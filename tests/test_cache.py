"""Tests of the persistent compile cache (``repro.sweep.cache``).

The load-bearing claims, in order of how expensive they'd be to lose:

* **Warm is bit-identical to cold.**  A fresh-cache run and a
  disk-served rerun of the same point/cell produce identical hits, finals,
  and histories — for the sequential path, the batched path, and the
  batch-bucket-padded batched path (padding rows ride the vmapped scan but
  must never perturb real rows).
* **Stale and corrupt entries recompile, loudly.**  A code-hash change
  rotates every key; garbage bytes under a valid key are detected,
  reported on stderr, deleted, and recompiled — never silently executed.
* **Keys don't collide across statics.**  Every parameter that changes the
  traced program must change ``program_key`` — a collision would silently
  run the wrong executable (the per-entry key-material check is the second
  line of defense, also covered here).
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import engine as engine_lib
from repro.sweep import cache as cache_lib
from repro.sweep import grid
from repro.sweep import run as sweep_run

POINT = dict(n=4, K=2, sigma=0.5, max_rounds=20, eval_every=10, eps=0.0)


def _cache(tmp_path, **kw):
    return cache_lib.CompileCache(str(tmp_path / "aot"), **kw)


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

def test_bucket_batch():
    assert [cache_lib.bucket_batch(b) for b in (1, 2, 3, 5, 8)] == \
        [1, 2, 4, 8, 8]
    assert cache_lib.bucket_batch(9) == 16 or cache_lib.bucket_batch(9) % 8 == 0
    assert cache_lib.bucket_batch(17) == 24  # multiples of 8 past 8


def test_length_schedule():
    assert cache_lib.length_schedule(10) == (8, 2)
    assert cache_lib.length_schedule(8) == (8,)
    assert cache_lib.length_schedule(13) == (8, 4, 1)
    assert cache_lib.length_schedule(0) == ()
    for n in range(1, 40):
        assert sum(cache_lib.length_schedule(n)) == n


# ---------------------------------------------------------------------------
# warm == cold, bit for bit
# ---------------------------------------------------------------------------

def test_run_point_warm_bit_identical(tmp_path):
    base = sweep_run.run_point(POINT, cache=None)
    cold_cache = _cache(tmp_path)
    cold = sweep_run.run_point(POINT, cache=cold_cache)
    assert cold_cache.stats["misses"] > 0 and cold_cache.stats["puts"] > 0
    # a fresh CompileCache on the same root simulates a new process: every
    # executable must come from disk
    warm_cache = _cache(tmp_path)
    warm = sweep_run.run_point(POINT, cache=warm_cache)
    assert warm_cache.stats["hits"] > 0
    assert warm_cache.stats["misses"] == 0
    assert warm_cache.stats["errors"] == 0
    for a, b in ((base, cold), (cold, warm)):
        assert a[0] == b[0]          # rounds_to_eps
        assert a[1] == b[1]          # final grad, exact float equality
        assert a[3] == b[3]          # full history


def test_run_cell_warm_and_padded_bit_identical(tmp_path):
    # B=3 pads to the 4-bucket under the cache: the padded program must
    # reproduce the unpadded cache-off results bit for bit
    spec = grid.GridSpec(name="t", base=dict(POINT, eps=0.35, sigma=0.0),
                         axes=(grid.batch_axis("heterogeneity",
                                               0.0, 1.0, 3.0),))
    [cell] = spec.cells()
    base_results, _ = sweep_run.run_cell(cell, cache=None)
    cold_cache = _cache(tmp_path)
    cold_results, _ = sweep_run.run_cell(cell, cache=cold_cache)
    assert base_results == cold_results
    warm_cache = _cache(tmp_path)
    warm_results, _ = sweep_run.run_cell(cell, cache=warm_cache)
    assert warm_cache.stats["misses"] == 0
    assert warm_cache.stats["hits"] > 0
    assert warm_results == cold_results
    # the final trajectories slice back to the real batch
    (_, _), trajs = sweep_run.run_cell(cell, cache=_cache(tmp_path),
                                       return_trajs=True)
    assert trajs.state.x.shape[0] == len(cell.points)


def test_pad_trajectories_freezes_padding():
    p = sweep_run._full_point(dict(POINT, n=4))
    traj, _ = sweep_run.prepare_trajectory(p)
    from repro.sweep import batched as batched_lib

    stacked = batched_lib.tree_stack([traj, traj])
    padded = cache_lib.pad_trajectories(stacked, 2)
    assert padded.state.x.shape[0] == 4
    assert padded.active.tolist() == [True, True, False, False]


# ---------------------------------------------------------------------------
# invalidation: stale code, corrupt entries
# ---------------------------------------------------------------------------

def test_stale_code_hash_forces_recompile(tmp_path, monkeypatch):
    cold = _cache(tmp_path)
    sweep_run.run_point(POINT, cache=cold)
    assert cold.stats["puts"] > 0
    monkeypatch.setitem(cache_lib._CODE_HASH, "hash", "deadbeef00000000")
    stale = _cache(tmp_path)
    sweep_run.run_point(POINT, cache=stale)
    # every lookup must miss: the old entries keyed the old sources
    assert stale.stats["hits"] == 0
    assert stale.stats["misses"] > 0


def test_corrupt_entry_recovers_loudly(tmp_path, capsys):
    cold = _cache(tmp_path)
    expected = sweep_run.run_point(POINT, cache=cold)
    root = tmp_path / "aot"
    entries = sorted(root.glob("*.aotc"))
    assert entries
    for entry in entries:
        entry.write_bytes(b"not a cache entry")
    warm = _cache(tmp_path)
    got = sweep_run.run_point(POINT, cache=warm)
    err = capsys.readouterr().err
    assert "[compile-cache]" in err and "corrupt" in err
    assert warm.stats["errors"] == len(entries)
    assert warm.stats["hits"] == 0 and warm.stats["misses"] > 0
    # corrupt files were deleted and rewritten with good entries
    assert warm.stats["puts"] == len(entries)
    assert got[1] == expected[1] and got[3] == expected[3]


def test_key_material_mismatch_is_loud(tmp_path, capsys):
    # hash collisions / key-construction bugs: an entry whose embedded
    # material disagrees with the lookup's must be rejected, not executed
    cache = _cache(tmp_path)
    fn = jax.jit(lambda x: x + 1)
    args = (jnp.ones((4,)),)
    compiled, info = cache.get_or_compile("t", ("a",), fn, args)
    assert info["source"] == "compile"
    key = cache_lib.program_key("t", ("a",), args)
    other = cache_lib.key_material("t", ("b",), args)
    assert cache.load(key, other) is None
    assert "mismatch" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# key hygiene: distinct statics -> distinct keys
# ---------------------------------------------------------------------------

def test_program_key_separates_statics():
    args = (jnp.ones((4, 10)),)
    variants = [
        ("chunk", (("n", 8), ("algorithm", "kgt_minimax"))),
        ("chunk", (("n", 16), ("algorithm", "kgt_minimax"))),
        ("chunk", (("n", 8), ("algorithm", "local_sgda"))),
        ("preparer", (("n", 8), ("algorithm", "kgt_minimax"))),
        ("phi_eval", (("n", 8), ("algorithm", "kgt_minimax"))),
    ]
    keys = {cache_lib.program_key(kind, statics, args)
            for kind, statics in variants}
    assert len(keys) == len(variants)
    # avals key too: same statics, different shapes
    assert cache_lib.program_key("chunk", variants[0][1],
                                 (jnp.ones((8, 10)),)) not in keys


def test_program_statics_cover_cell_parameters():
    """_program_statics must differ whenever a parameter that changes the
    traced cell program differs — the key-collision regression net for the
    sweep path (and ``_PREPARERS``' key is a subset of these)."""
    base = sweep_run._full_point(dict(POINT))
    seen = {sweep_run._program_statics(base, batched=False)}
    for delta in (dict(n=8), dict(K=4), dict(algorithm="local_sgda"),
                  dict(topology="full"), dict(mixing_impl="gather"),
                  dict(sigma=0.0), dict(topology_family="erdos_renyi"),
                  dict(participation=0.5), dict(num_byzantine=1),
                  dict(gossip_compress="int8"), dict(robust_trim=2),
                  dict(gossip_backend="xla")):
        statics = sweep_run._program_statics(
            sweep_run._full_point(dict(POINT, **delta)), batched=False)
        assert statics not in seen, delta
        seen.add(statics)
    # batched vs sequential never share an executable
    assert sweep_run._program_statics(base, batched=True) not in seen
    # ...but eps / round budgets deliberately DO share one
    assert sweep_run._program_statics(
        sweep_run._full_point(dict(POINT, eps=0.1, max_rounds=100)),
        batched=False) in seen


def test_chunk_lengths_key_separately(tmp_path):
    """timed_chunk_builder folds the scan length into the cache key: two
    lengths of the same cell must be two entries, not one collision."""
    cache = _cache(tmp_path)

    def fake_build(length):
        return jax.jit(lambda s, f: (s + length, None))

    build = engine_lib.timed_chunk_builder(fake_build, cache=cache,
                                           statics=(("cell", "t"),))
    s = jnp.float32(0.0)
    s, _ = build(2)(s, jnp.int32(0))
    s, _ = build(3)(s, jnp.int32(0))
    assert float(s) == 5.0
    assert cache.stats["misses"] == 2 and cache.stats["puts"] == 2
    # a fresh cache on the same root serves both lengths from disk and
    # executes the right program for each
    cache2 = _cache(tmp_path)
    build2 = engine_lib.timed_chunk_builder(fake_build, cache=cache2,
                                            statics=(("cell", "t"),))
    s2 = jnp.float32(0.0)
    s2, _ = build2(2)(s2, jnp.int32(0))
    s2, _ = build2(3)(s2, jnp.int32(0))
    assert float(s2) == 5.0
    assert cache2.stats["hits"] == 2 and cache2.stats["misses"] == 0


# ---------------------------------------------------------------------------
# satellites: _timed_eval fallback, timing discipline, clock hygiene
# ---------------------------------------------------------------------------

def test_timed_eval_fallback_is_loud_and_uncharged(capsys):
    class BrokenJit:
        """Quacks like jax.jit but cannot AOT-compile."""

        def lower(self, *args):
            raise RuntimeError("no lowering for you")

        def __call__(self, x):
            return x + 1

    counters = []

    class Tel:
        def counter(self, name, value, **attrs):
            counters.append((name, value, attrs))

    call = sweep_run._timed_eval(BrokenJit(), telemetry=Tel())
    assert int(call(jnp.int32(1))) == 2
    # the failed attempt is NOT charged to compile_s...
    assert call.stats["compile_s"] == 0.0
    # ...and the fallback is loud on both channels
    assert "falling back to on-demand jit" in capsys.readouterr().err
    assert counters and counters[0][0] == "eval_aot_fallback"


def test_run_point_timing_rounded_and_clamped(tmp_path):
    # a fully-warm cached run is the regression trigger: compile_s + setup_s
    # routinely round to within a ms of wall_s, which drove run_s negative
    cache = _cache(tmp_path)
    sweep_run.run_point(POINT, cache=cache)
    _, _, timing, _ = sweep_run.run_point(POINT, cache=cache)
    assert timing["run_s"] >= 0.0
    for key, value in timing.items():
        assert value == round(value, 3), (key, value)


def test_no_wall_clock_stamps_in_timing_paths():
    """The PR-7 eviction of time.time() from timing code, held for the
    modules this PR fixed (engine, sweep runner, launch drivers)."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "repro")
    for rel in ("engine/engine.py", "sweep/run.py", "sweep/cache.py",
                "launch/train.py", "launch/dryrun.py"):
        with open(os.path.join(src, rel)) as f:
            assert "time.time()" not in f.read(), rel


# ---------------------------------------------------------------------------
# env plumbing
# ---------------------------------------------------------------------------

def test_resolve_env_off_values(monkeypatch):
    for off in ("", "0", "off", "none"):
        monkeypatch.setenv(cache_lib.ENV_CACHE, off)
        assert cache_lib.from_env() is None
    monkeypatch.delenv(cache_lib.ENV_CACHE)
    assert cache_lib.from_env() is None  # unset: no default-on ambush
    assert cache_lib.resolve(None) is None


def test_resolve_env_path(monkeypatch, tmp_path):
    monkeypatch.setenv(cache_lib.ENV_CACHE, str(tmp_path / "c"))
    cache = cache_lib.resolve(cache_lib.UNSET)
    assert cache is not None
    assert cache.root == str(tmp_path / "c" / "aot")
    # memoized per env value: run_point calls share one executable memo
    assert cache_lib.resolve(cache_lib.UNSET) is cache


def _run_env(code: str, **env):
    import subprocess

    full = {k: v for k, v in os.environ.items()
            if k not in (cache_lib.ENV_JAX_CACHE_DIR, cache_lib.ENV_CACHE)}
    full.update(env, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    full["PYTHONPATH"] = os.path.join(root, "src")
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=root,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("explicit", [False, True])
@pytest.mark.parametrize("outside", [False, True])
def test_enable_xla_cache_placement(tmp_path, outside, explicit):
    """A directory the caller names wins; else $JAX_COMPILATION_CACHE_DIR
    is left as jax read it; else jax's cache goes to the fixed
    results/.xla_cache/xla.  The AOT layer's default root stays inside the
    checkout whatever the environment says.  A fresh process each: the
    placement is global jax config."""
    env = ({cache_lib.ENV_JAX_CACHE_DIR: str(tmp_path / "env")}
           if outside else {})
    arg = repr(str(tmp_path / "named")) if explicit else ""
    proc = _run_env(
        "import jax\n"
        "from repro.sweep import cache as c\n"
        f"print(c.enable_xla_cache({arg}))\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(c.default_root())\n", **env)
    assert proc.returncode == 0, proc.stderr
    active, configured, root = proc.stdout.split()
    in_repo = os.path.join(cache_lib.repo_root(), "results", ".xla_cache")
    if explicit:
        want = str(tmp_path / "named")
    elif outside:
        want = str(tmp_path / "env")
    else:
        want = os.path.join(in_repo, "xla")
    assert active == configured == want
    assert root == in_repo


def test_train_library_leaves_the_cache_alone():
    """train() turns on no persistent cache by itself: only the CLI's
    main() (and chip_smoke.py) make that choice for the process."""
    proc = _run_env(
        "import jax\n"
        "from repro.launch import train as t\n"
        "args = t.build_parser().parse_args(\n"
        "    ['--arch', 'paper-toy', '--reduced', '--rounds', '1',\n"
        "     '--clients', '2', '--local-steps', '1', '--batch', '1',\n"
        "     '--seq-len', '16', '--chunk', '1', '--log-every', '1'])\n"
        "res = t.train(args)\n"
        "print(sorted(res))\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    keys, cache_dir = proc.stdout.strip().splitlines()[-2:]
    assert keys == "['final_consensus', 'history']"
    assert cache_dir == "None"


def test_trainer_writes_its_cache_only_where_jax_is_told(tmp_path):
    """A CPU run of the trainer CLI with $JAX_COMPILATION_CACHE_DIR set
    persists its programs there and writes nothing beside it (the AOT
    layer stays off unless asked for)."""
    xla = tmp_path / "xla"
    proc = _run_env(
        "from repro.launch.train import main\n"
        "import sys\n"
        "sys.argv = ['train', '--arch', 'paper-toy', '--reduced',\n"
        "            '--rounds', '2', '--clients', '2', '--local-steps',\n"
        "            '1', '--batch', '1', '--seq-len', '16', '--chunk', '2',\n"
        "            '--log-every', '1']\n"
        "main()\n", **{cache_lib.ENV_JAX_CACHE_DIR: str(xla)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert os.listdir(tmp_path) == ["xla"]
    assert len(os.listdir(xla)) > 0
