"""Puts the benchmark's modules and the system under test on the path.

Run from the repository root:  python -m pytest -q bench/tests
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _own_compile_cache(tmp_path_factory):
    """Runs driven from the tests keep JAX's persistent cache in a
    directory of their own, not in the checkout's."""
    import run as bench_run

    bench_run.CACHE_DIR = str(tmp_path_factory.mktemp("xla_cache"))
