"""Persistent compilation cache for sweep cells and engine chunk programs.

bench_sweep's diagnosis (ROADMAP: "Kill compile time as the sweep
bottleneck"): the sequential sweep path spends ~48 s of a 53 s wall in XLA
compilation, and even the batched path compiles for seconds to run for
sub-seconds.  Every process recompiles every static cell from scratch, so
"run the paper grid on every PR" is priced in compiler time, not math.
This module removes that price in three layers:

1. **jax's built-in persistent compilation cache** — :func:`enable_xla_cache`
   turns it on with the size/time thresholds dropped to zero, so a repeated
   ``lower().compile()`` skips the XLA backend compile.  A directory the
   caller names wins; else, where ``$JAX_COMPILATION_CACHE_DIR`` is set,
   jax has already read it and that directory is used as it is; otherwise
   the cache lives at the fixed ``results/.xla_cache/xla``.  The process
   still pays tracing + lowering per program, which is why layer 2 exists.

2. **An AOT executable cache** — :class:`CompileCache` serializes
   ``jax.jit(...).lower(...).compile()`` executables
   (``jax.experimental.serialize_executable``) to disk, keyed on a stable
   signature: the program *kind* + the static-cell statics tuple + the
   abstract avals (shape/dtype/pytree structure) of the example arguments +
   the jax version/backend fingerprint + a content hash of the git-tracked
   ``repro.core`` / ``repro.engine`` / ``repro.kernels`` / ``repro.sweep``
   sources (:func:`code_hash`).  A warm process deserializes in ~30 ms what
   cold-compiles in seconds, and **skips tracing and lowering entirely**.
   A code change rotates the key (stale entries are simply never hit); a
   corrupt or checksum-failing entry is reported loudly on stderr, deleted,
   and recompiled.  Entries embed their full key material and are verified
   on load, so a key-construction bug surfaces as a loud mismatch instead
   of a silent wrong-program execution.

   Keys deliberately contain **only** information that determines the traced
   program: anything baked into the jaxpr as a closure constant must be in
   the statics tuple (the sweep paths qualify because PR 4 made every
   per-point quantity a traced operand; callers with baked data — e.g. the
   train driver's data model — must fold the generating config into
   ``statics``, see ``launch/train.py``).

3. **Shape-bucket reuse** — cells differing only in paddable dimensions
   share one executable instead of recompiling per shape:

   * :func:`bucket_batch` pads the vmapped cell's trajectory axis up to the
     next power of two (≤ 8) / multiple of 8 — the same n→8 sublane
     discipline the Pallas kernels apply internally — with padding
     trajectories frozen by the existing ``active`` mask, so a 5-point and
     a 7-point cell both run the B=8 program (``pad_trajectories``; vmap is
     slice-bit-stable for the scan programs, so real rows are unchanged —
     tests/test_cache.py pins that).  Inside the kernels the n→8 / dz→128
     padding already happens pre-``pallas_call``, so kernel programs bucket
     for free once their callers do.
   * :func:`length_schedule` decomposes an arbitrary scan length into
     descending powers of two (10 → 8+2), so cells differing only in
     ``eval_every`` / ``max_rounds`` remainders draw from one small shared
     pool of chunk executables instead of compiling per distinct length.
     Splitting a scan at a chunk boundary is bit-exact: the carried state
     is identical and the per-round bodies key off ``state.round``.

Environment plumbing (respected by the sweep CLI, ``launch/train`` and
``launch/dryrun``):

* ``JAX_COMPILATION_CACHE_DIR`` — jax's own variable: where layer 1 lives
  unless a directory is named explicitly.  The AOT layer stays inside the
  checkout (``results/.xla_cache/aot``) whatever it says.
* ``REPRO_COMPILE_CACHE`` — ``off``/``0`` disables; a path roots the whole
  stack (``<path>/aot`` + ``<path>/xla``); ``1``/``on``/``auto`` uses the
  default AOT root and layer 1's default placement.

Cache traffic is observable: hit/miss/error/put counters and byte totals
flow through ``repro.obs`` as ``compile_cache.*`` counter events (folded
into a ``compile_cache`` block by ``repro.obs.report``), and
``sweep/store.py`` stamps the same stats into every stored sweep's
provenance.

Entries are pickles — treat a cache directory with the same trust as the
code that wrote it (it is a local build artifact, not an interchange
format).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import re
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

CACHE_VERSION = 1

ENV_CACHE = "REPRO_COMPILE_CACHE"
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_OFF_VALUES = ("", "0", "off", "none", "false", "disabled")
_ON_VALUES = ("1", "on", "auto", "true")

#: Packages whose sources key the executables (a change in any of them must
#: rotate every cached program — they define the traced computations).
CODE_HASH_PACKAGES = ("core", "engine", "kernels", "sweep")


def repo_root() -> str:
    from repro.sweep import store as store_lib

    return store_lib.repo_root()


def default_root() -> str:
    """``<repo>/results/.xla_cache`` — gitignored scratch, like the rest of
    ``results/`` outside the curated artifacts."""
    return os.path.join(repo_root(), "results", ".xla_cache")


# ---------------------------------------------------------------------------
# layer 1: jax's built-in persistent compilation cache


def enable_xla_cache(root: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    An explicit ``root`` wins.  Without one, where
    ``$JAX_COMPILATION_CACHE_DIR`` is set, jax has read it at import and its
    setting is left alone; otherwise the cache goes to
    ``results/.xla_cache/xla``.  Thresholds are dropped so even the sweep's
    sub-second programs persist.  Idempotent — safe to call from every
    entry point."""
    outside = os.environ.get(ENV_JAX_CACHE_DIR)
    if root is None and outside:
        root = outside
    else:
        root = root or os.path.join(default_root(), "xla")
        jax.config.update("jax_compilation_cache_dir", root)
    os.makedirs(root, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return root


# ---------------------------------------------------------------------------
# key material


_CODE_HASH: Dict[str, str] = {}


def _git_tracked_sources() -> Optional[list]:
    rel = [f"src/repro/{p}" for p in CODE_HASH_PACKAGES]
    try:
        out = subprocess.run(
            ["git", "ls-files", "--", *rel], cwd=repo_root(),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    files = [ln for ln in out.stdout.splitlines() if ln.strip()]
    return sorted(files) or None


def _walked_sources() -> list:
    files = []
    root = repo_root()
    for pkg in CODE_HASH_PACKAGES:
        base = os.path.join(root, "src", "repro", pkg)
        for dirpath, _, names in os.walk(base):
            for name in names:
                if name.endswith(".py"):
                    files.append(os.path.relpath(
                        os.path.join(dirpath, name), root))
    return sorted(files)


def code_hash() -> str:
    """Content hash of the ``repro.core``/``engine``/``kernels``/``sweep``
    sources — the part of the cache key that invalidates every executable
    when the programs they encode change.  Git-tracked file list when
    available (uncommitted edits still hash through the file *contents*),
    plain package walk otherwise.  Memoized per process."""
    if "hash" in _CODE_HASH:
        return _CODE_HASH["hash"]
    files = _git_tracked_sources() or _walked_sources()
    h = hashlib.sha256()
    root = repo_root()
    for rel in files:
        path = os.path.join(root, rel)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            blob = b"<unreadable>"
        h.update(rel.encode())
        h.update(b"\0")
        h.update(blob)
        h.update(b"\0")
    _CODE_HASH["hash"] = h.hexdigest()[:16]
    return _CODE_HASH["hash"]


def backend_fingerprint() -> Tuple[str, ...]:
    """What the serialized executable is only valid for: jax version,
    platform, device kind, and local device count (the executable embeds
    its device assignment)."""
    dev = jax.devices()[0]
    return (jax.__version__, dev.platform,
            str(getattr(dev, "device_kind", "")), str(jax.device_count()))


def _freeze(obj: Any) -> Any:
    """Canonical hashable/repr-stable form of a statics structure."""
    if isinstance(obj, dict):
        return tuple((str(k), _freeze(v)) for k, v in sorted(obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def _aval_signature(args: tuple) -> Tuple[str, Tuple]:
    """(pytree structure, per-leaf (shape, dtype)) of the example call —
    the shape half of the key.  Non-array leaves key on their repr."""
    leaves, treedef = jax.tree.flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            sig.append(("pyleaf", repr(leaf)))
    return str(treedef), tuple(sig)


def key_material(kind: str, statics: Any, args: tuple) -> tuple:
    """The full, human-inspectable tuple the key hashes (also embedded in
    every cache entry and verified on load)."""
    treedef, avals = _aval_signature(args)
    return (CACHE_VERSION, kind, _freeze(statics), treedef, avals,
            code_hash(), backend_fingerprint())


def program_key(kind: str, statics: Any, args: tuple) -> str:
    return hashlib.sha256(repr(key_material(kind, statics, args))
                          .encode()).hexdigest()


# ---------------------------------------------------------------------------
# layer 3: shape buckets


def bucket_batch(b: int) -> int:
    """Trajectory-batch bucket: next power of two up to 8, then multiples
    of 8 — mirroring the kernels' n→8 sublane padding, so cells whose point
    counts differ only within a bucket share one vmapped executable."""
    b = int(b)
    if b <= 1:
        return 1
    if b <= 8:
        return 1 << (b - 1).bit_length()
    return -(-b // 8) * 8


def length_schedule(length: int) -> Tuple[int, ...]:
    """Decompose a scan length into descending powers of two (10 → (8, 2)).
    Chunks compose bit-exactly, so any ``eval_every``/remainder length is
    served from O(log length) shared executables."""
    length = int(length)
    if length <= 0:
        return ()
    out = []
    p = 1 << (length.bit_length() - 1)
    while length:
        if p <= length:
            out.append(p)
            length -= p
        p >>= 1
    return tuple(out)


def pad_trajectories(trajs, pad: int):
    """Pad the stacked trajectory axis with ``pad`` copies of trajectory 0,
    frozen from round 0 by ``active=False`` — the batch-bucket filler.  The
    padding rows still flow through the scan (vmap has no per-slice control
    flow) but their state never changes, and callers slice results back to
    the real batch."""
    if pad <= 0:
        return trajs
    new = jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad, *x.shape[1:]))]), trajs)
    active = jnp.concatenate(
        [trajs.active, jnp.zeros((pad,), trajs.active.dtype)])
    return dataclasses.replace(new, active=active)


# ---------------------------------------------------------------------------
# layer 2: the AOT executable cache


def _loud(msg: str) -> None:
    print(f"[compile-cache] {msg}", file=sys.stderr, flush=True)


def _scan_custom_calls(compiled) -> Tuple[str, ...]:
    """The custom-call targets of a compiled executable's optimized HLO.

    XLA resolves these *by name at call time with no existence check*: a
    deserialized executable whose targets nobody registered in this process
    segfaults instead of raising.  jax registers them as a side effect of
    *lowering* the originating op (e.g. the LAPACK qr/svd family on first
    ``jnp.linalg`` trace) — exactly the step the AOT cache skips — so every
    entry records its targets and :func:`_ensure_runtime` re-registers them
    before the executable is loaded.  ``("?",)`` when the executable cannot
    be introspected (best-effort warmup applies).
    """
    try:
        mods = compiled._executable.xla_executable.hlo_modules()
        txt = "\n".join(m.to_string() for m in mods)
    except Exception:
        return ("?",)
    return tuple(sorted(set(
        re.findall(r'custom_call_target="([^"]+)"', txt))))


def _ensure_runtime(targets: Tuple[str, ...]) -> bool:
    """Register the runtime handlers for ``targets`` in this process, or
    report False (the caller recompiles instead of risking a segfault)."""
    for t in targets:
        if t.startswith("lapack_") or t.startswith("blas_") or t == "?":
            # importing jaxlib.lapack runs its register_custom_call_target
            # loop, and initialize() binds the scipy-provided kernel
            # pointers the handlers dispatch to — jax normally does both
            # lazily inside the linalg *lowering* rules this cache skips
            import jaxlib.lapack

            jaxlib.lapack._lapack.initialize()
        else:
            return False
    return True


class CompileCache:
    """Disk cache of serialized XLA executables + an in-process memo.

    ``get_or_compile(kind, statics, fn, args)`` returns a callable with the
    same signature as ``fn`` — a memoized executable, a deserialized disk
    entry, or a freshly AOT-compiled (and stored) one, in that order —
    plus an info dict (``source`` ∈ memo/disk/compile/fallback, and the
    seconds spent compiling/deserializing).  ``fn`` must be a ``jax.jit``
    product (anything exposing ``.lower(*args).compile()``); a plain
    callable passes through untouched as ``source="uncacheable"``.

    ``telemetry`` (a ``repro.obs.Telemetry``) receives ``compile_cache.*``
    counters per event; ``stats`` accumulates the same numbers in-process.
    """

    def __init__(self, root: Optional[str] = None, *, telemetry=None,
                 bucket_batch: bool = True, bucket_lengths: bool = True):
        self.root = root or os.path.join(default_root(), "aot")
        self.telemetry = telemetry
        self.bucket_batch = bucket_batch
        self.bucket_lengths = bucket_lengths
        self.memo: Dict[str, Any] = {}
        self.stats: Dict[str, float] = {
            "hits": 0, "misses": 0, "errors": 0, "puts": 0, "memo_hits": 0,
            "bytes_read": 0, "bytes_written": 0,
            "compile_s": 0.0, "deserialize_s": 0.0,
        }

    # -- bookkeeping --------------------------------------------------------

    def _count(self, name: str, value=1, **attrs) -> None:
        self.stats[name] = self.stats.get(name, 0) + value
        if self.telemetry is not None:
            self.telemetry.counter(f"compile_cache.{name}", value, **attrs)

    def describe(self) -> dict:
        """Provenance-grade snapshot (``sweep/store.py`` stamps this)."""
        out = {"root": self.root, "code_hash": code_hash(),
               "cache_version": CACHE_VERSION}
        out.update({k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in self.stats.items()})
        return out

    # -- disk entries -------------------------------------------------------

    def _path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.aotc")

    def load(self, key: str, material: tuple):
        """The executable stored under ``key``, or None (miss).  Corrupt,
        truncated, checksum-failing, or key-mismatched entries are deleted
        and reported loudly — the caller recompiles."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            self._count("errors", key=key)
            _loud(f"unreadable entry {path} ({e}); recompiling")
            return None
        t0 = time.perf_counter()
        try:
            entry = pickle.loads(blob)
            if entry["version"] != CACHE_VERSION:
                raise ValueError(f"cache version {entry['version']} != "
                                 f"{CACHE_VERSION}")
            if entry["material"] != repr(material):
                raise ValueError("key material mismatch (hash collision or "
                                 "key-construction bug)")
            payload = entry["payload"]
            if hashlib.sha256(payload).hexdigest() != entry["checksum"]:
                raise ValueError("payload checksum mismatch")
            targets = tuple(entry.get("custom_calls", ("?",)))
            if not _ensure_runtime(targets):
                raise ValueError(
                    f"cannot register custom-call targets {targets} in "
                    "this process (calling the executable would crash)")
            from jax.experimental import serialize_executable as se

            loaded = se.deserialize_and_load(
                payload, entry["in_tree"], entry["out_tree"])
        except Exception as e:  # corrupt/stale in any way -> recompile loudly
            self._count("errors", key=key)
            _loud(f"corrupt entry {path} ({type(e).__name__}: {e}); "
                  "deleting and recompiling")
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        dur = time.perf_counter() - t0
        self.stats["deserialize_s"] += dur
        self._count("hits", kind=material[1])
        self._count("bytes_read", len(blob), kind=material[1])
        return loaded, dur

    def store(self, key: str, material: tuple, compiled) -> None:
        """Serialize ``compiled`` under ``key`` (atomic tmp+rename write —
        concurrent sweep processes at worst both write the same bytes).
        Failures are loud but non-fatal: the run proceeds uncached."""
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps({
                "version": CACHE_VERSION,
                "material": repr(material),
                "checksum": hashlib.sha256(payload).hexdigest(),
                "custom_calls": _scan_custom_calls(compiled),
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            os.makedirs(self.root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._path(key))
        except Exception as e:
            self._count("errors", key=key)
            _loud(f"failed to store entry {key[:12]}… "
                  f"({type(e).__name__}: {e}); run proceeds uncached")
            return
        self._count("puts", kind=material[1])
        self._count("bytes_written", len(blob), kind=material[1])

    # -- the main entry point ----------------------------------------------

    def get_or_compile(self, kind: str, statics: Any, fn, args: tuple):
        """See class docstring.  Returns ``(callable, info)``."""
        lower = getattr(fn, "lower", None)
        if lower is None:
            return fn, {"source": "uncacheable",
                        "compile_s": 0.0, "deserialize_s": 0.0}
        material = key_material(kind, statics, args)
        key = program_key(kind, statics, args)
        if key in self.memo:
            self._count("memo_hits", kind=kind)
            return self.memo[key], {"source": "memo",
                                    "compile_s": 0.0, "deserialize_s": 0.0}
        hit = self.load(key, material)
        if hit is not None:
            loaded, dur = hit
            self.memo[key] = loaded
            return loaded, {"source": "disk",
                            "compile_s": 0.0, "deserialize_s": dur}
        self._count("misses", kind=kind)
        t0 = time.perf_counter()
        try:
            compiled = lower(*args).compile()
        except Exception as e:
            _loud(f"AOT lowering failed for {kind} "
                  f"({type(e).__name__}: {e}); falling back to on-demand jit")
            self._count("errors", kind=kind)
            return fn, {"source": "fallback",
                        "compile_s": 0.0, "deserialize_s": 0.0}
        dur = time.perf_counter() - t0
        self.stats["compile_s"] += dur
        self.store(key, material, compiled)
        self.memo[key] = compiled
        return compiled, {"source": "compile",
                          "compile_s": dur, "deserialize_s": 0.0}


# ---------------------------------------------------------------------------
# defaults / env resolution


#: Sentinel for "no explicit cache argument": resolve from the environment.
UNSET = object()

_DEFAULT: Dict[str, Any] = {}


def from_env(telemetry=None) -> Optional[CompileCache]:
    """The process-wide default cache per ``$REPRO_COMPILE_CACHE`` (None
    when unset/off).  Memoized so repeated ``run_point`` calls share one
    executable memo; setting the env var also arms layer 1 under the same
    root."""
    value = os.environ.get(ENV_CACHE)
    if value is None or value.strip().lower() in _OFF_VALUES:
        return None
    if value in _DEFAULT:
        cache = _DEFAULT[value]
    else:
        if value.strip().lower() in _ON_VALUES:
            enable_xla_cache()
            cache = CompileCache()
        else:
            enable_xla_cache(os.path.join(value, "xla"))
            cache = CompileCache(os.path.join(value, "aot"))
        _DEFAULT[value] = cache
    if telemetry is not None:
        cache.telemetry = telemetry
    return cache


def resolve(cache, telemetry=None) -> Optional[CompileCache]:
    """Normalize a ``cache=`` keyword: :data:`UNSET` → env default,
    None → disabled, a :class:`CompileCache` → itself."""
    if cache is UNSET:
        return from_env(telemetry)
    if cache is not None and telemetry is not None and cache.telemetry is None:
        cache.telemetry = telemetry
    return cache
