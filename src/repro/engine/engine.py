"""Chunked scan-over-rounds execution engine.

The host training loop pays per-round costs that have nothing to do with
Algorithm 1: host-side batch sampling, host→device transfer, one jit
dispatch per round, and a blocking metrics read.  For the
thousands-of-rounds × K-local-steps trajectories the paper's experiments
run, that overhead dominates wall-clock on fast hardware.

This engine compiles **R-round chunks as a single XLA program**:

  * ``lax.scan`` over ``round_step`` — one dispatch per R rounds;
  * a device-side *sampler* ``(round_idx) -> (batches, keys)`` called inside
    the scan body, so each round's data is generated on device
    (``repro.engine.sampler``; no per-round host→device transfer);
  * *streaming diagnostics* — a fixed-size on-device metrics buffer
    ``(mask, rounds, rows)`` of length R, filled every ``log_every`` rounds
    by ``metrics_fn`` inside the scan (a ``lax.cond`` skips the compute on
    non-logged rounds) and read back **once per chunk**;
  * chunk-boundary *hooks* (checkpointing, …).  ``state.round`` is the
    single source of truth: the sampler, the lr schedule (``lr_scale``
    inside ``round_step``), and the metrics gating are all functions of it,
    so a restored checkpoint resumes the identical trajectory.

Observability: the chunk program names its parts with ``jax.named_scope``
(``engine.sampler``, ``engine.metrics``; the round step adds its own
``kgt.*`` scopes), which only adds ``op_name`` metadata to the compiled
instructions, and :func:`run` wraps its host phases in telemetry spans
(``engine.dispatch``, ``engine.readback``, ``engine.hooks``,
``engine.compile``), which are ``jax.profiler`` annotations on the device
trace's clock — see docs/architecture.md, "Observability".

Layering: this module is algorithm- and problem-agnostic — it only needs a
``round_step(state, batches, keys) -> state`` with an integer
``state.round`` field, a sampler, and (optionally) a metrics function
returning a flat ``{name: array}`` dict.  ``repro.launch.train`` drives the
DRO-LM runs through it, ``repro.launch.steps.build_train_chunk`` compiles
the same chunk program with donated sharded state over the decentralized
mesh, and ``benchmarks/``/``examples/`` consume it for the paper-toy
trajectories.
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

# (round_idx) -> (batches, keys) or (batches, keys, extras): a sampler may
# return a third element — a tuple of per-round traced operands (a sampled
# mixing matrix W, a participation mask; see sampler.with_topology) that the
# chunk body splats into round_step(state, batches, keys, *extras).
Sampler = Callable[[jnp.ndarray], Tuple[Any, ...]]
MetricsFn = Callable[[Any, Any], Dict[str, jnp.ndarray]]
Hook = Callable[[Any, List[dict], int], None]  # (state, records, prev_round)


def split_sampled(sampled) -> Tuple[Any, Any, Tuple[Any, ...]]:
    """One sampler return -> ``(batches, keys, extras)`` per the Sampler
    protocol above.  Every consumer of a sampler (the scanned chunk body,
    the host A/B loops) goes through this so the two execution paths can't
    drift on the protocol."""
    batches, keys = sampled[0], sampled[1]
    extras = tuple(sampled[2]) if len(sampled) > 2 else ()
    return batches, keys, extras


def chunk_program(
    round_step: Callable[[Any, Any, Any], Any],
    sampler: Sampler,
    metrics_fn: Optional[MetricsFn] = None,
    *,
    log_every: int = 1,
    length: int,
):
    """Builds ``chunk_step(state, final_round) -> (state, buffer)``.

    ``buffer`` is ``None`` when ``metrics_fn`` is None, else the fixed-size
    on-device triple ``(mask (R,), rounds (R,), rows {name: (R, …)})``.
    A row is filled when the round index hits the ``log_every`` grid or
    equals ``final_round`` (so the last round of a run always logs) —
    matching the host driver's ``t % log_every == 0 or t == rounds-1``.
    """
    log_every = max(int(log_every), 1)

    def chunk_step(state, final_round):
        def body(st, _):
            with jax.named_scope("engine.sampler"):
                batches, keys, extras = split_sampled(sampler(st.round))
            new_st = round_step(st, batches, keys, *extras)
            if metrics_fn is None:
                return new_st, None
            do_log = jnp.logical_or(st.round % log_every == 0,
                                    st.round == final_round)
            shapes = jax.eval_shape(metrics_fn, new_st, batches)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            with jax.named_scope("engine.metrics"):
                row = jax.lax.cond(
                    do_log, lambda: metrics_fn(new_st, batches), lambda: zeros)
            return new_st, (do_log, st.round, row)

        state, buf = jax.lax.scan(body, state, None, length=length)
        return state, buf

    return chunk_step


def make_chunk_builder(
    round_step: Callable[[Any, Any, Any], Any],
    sampler: Sampler,
    metrics_fn: Optional[MetricsFn] = None,
    *,
    log_every: int = 1,
    donate: bool = True,
    jit_fn=None,
):
    """Returns ``build(length) -> jitted chunk_step``, caching per length.

    A run needs at most two lengths (full chunks + one remainder), so the
    cache stays tiny.  ``jit_fn(fn)`` overrides how the program is staged —
    ``build_train_chunk`` passes a mesh-aware jit with sharded/donated
    state; the default is a plain ``jax.jit`` with the state donated.
    """
    cache: Dict[int, Any] = {}

    def build(length: int):
        if length not in cache:
            fn = chunk_program(round_step, sampler, metrics_fn,
                               log_every=log_every, length=length)
            if jit_fn is not None:
                cache[length] = jit_fn(fn)
            else:
                cache[length] = jax.jit(
                    fn, donate_argnums=(0,) if donate else ())
        return cache[length]

    return build


def timed_chunk_builder(build_chunk: Callable[[int], Any], *,
                        cache=None, statics=None):
    """Wraps ``build(length)`` so compilation is timed apart from execution.

    The first call at each length goes through the jit AOT path
    (``fn.lower(*args).compile()``) with the elapsed time accumulated into
    ``wrapper.stats["compile_s"]``; subsequent calls hit the compiled
    executable directly.  This is what lets ``run`` / the benchmarks report
    steady-state ``run_s`` instead of folding first-chunk compilation into
    every rounds/s and time-to-ε number.

    ``cache`` (a ``repro.sweep.cache.CompileCache``) routes that AOT step
    through the persistent executable cache: the first call per length
    looks up ``(statics + length, arg avals)`` on disk and deserializes
    instead of compiling when warm.  The deserialize seconds are accumulated
    into ``compile_s`` (it is the get-an-executable cost the split exists to
    isolate), so a warm run reports compile_s ≈ milliseconds — the cache's
    own hit/miss/byte stats live on ``cache.stats``.  ``statics`` must name
    every value baked into the chunk program as a closure constant (see the
    cache module docstring); callers that cannot enumerate those must not
    pass a cache.

    When the built function has no ``lower`` (a plain Python callable), the
    whole first call — compile *and* its one execution — is attributed to
    ``compile_s``; for the multi-second XLA programs this wrapper exists to
    time, the execution share of that first call is noise.  A failed
    compile raises.  The first call's compile runs inside the profiler
    annotation ``engine.compile``.
    """
    wrapped: Dict[int, Any] = {}
    stats = {"compile_s": 0.0}

    def build(length: int):
        if length in wrapped:
            return wrapped[length]
        fn = build_chunk(length)
        holder: List[Any] = []

        def call(*args):
            if holder:
                return holder[0](*args)
            t0 = time.perf_counter()
            with obs.NULL.span("engine.compile"):
                if cache is not None:
                    compiled, info = cache.get_or_compile(
                        "chunk", (statics, ("length", length)), fn, args)
                    stats["compile_s"] += (info["compile_s"]
                                           + info["deserialize_s"])
                    holder.append(compiled)
                elif getattr(fn, "lower", None) is not None:
                    # a compile error propagates: retrying inside a plain
                    # call would only compile (and fail) a second time
                    holder.append(fn.lower(*args).compile())
                    stats["compile_s"] += time.perf_counter() - t0
                else:
                    holder.append(fn)
                    out = fn(*args)
                    jax.block_until_ready(out)
                    stats["compile_s"] += time.perf_counter() - t0
                    return out
            return holder[0](*args)

        wrapped[length] = call
        return call

    build.stats = stats
    return build


def row_to_record(row: Dict[str, Any], round_idx: int) -> dict:
    """One metrics row (host-side arrays) -> a plain-python history record:
    scalars become floats, vectors (e.g. per-group losses) become lists.
    Shared by the chunk read-back below and the per-round host loop so both
    execution models emit byte-identical record structures."""
    rec: dict = {"round": int(round_idx)}
    for name, v in row.items():
        v = np.asarray(v)
        rec[name] = float(v) if v.ndim == 0 else v.tolist()
    return rec


def records_from_buffer(buf) -> List[dict]:
    """Device metrics buffer -> list of plain-python history records.

    One transfer for the whole chunk; rows where the mask is unset (rounds
    that were not on the log grid) are dropped.
    """
    if buf is None:
        return []
    mask, rounds, rows = jax.device_get(buf)
    records = []
    for i in range(mask.shape[0]):
        if not bool(mask[i]):
            continue
        records.append(row_to_record(
            {name: col[i] for name, col in rows.items()}, rounds[i]))
    return records


def run(
    state,
    build_chunk: Callable[[int], Any],
    *,
    total_rounds: int,
    chunk_rounds: int,
    hooks: Sequence[Hook] = (),
    stop_fn: Optional[Callable[[List[dict]], bool]] = None,
    wall_clock: bool = True,
    boundary_every: Optional[int] = None,
    telemetry=None,
):
    """Drives chunks from ``state.round`` up to ``total_rounds``.

    Host work per chunk: one dispatch, one metrics read-back, hooks.  The
    resume point is read from ``state.round`` (a restored checkpoint picks
    up exactly where it left off).  Hooks are called at every chunk boundary
    as ``hook(state, records, prev_round)`` where ``prev_round`` is the
    round count before the chunk ran.  ``boundary_every=N`` splits chunks so
    a boundary lands on every multiple of N — pass the checkpoint cadence
    so ``checkpoint_hook`` fires at the exact requested rounds regardless
    of chunk alignment.  ``stop_fn(records) -> bool`` enables early exit at
    chunk boundaries (benchmarks' rounds-to-ε loops).

    Returns ``(state, history)`` with history records as produced by
    ``records_from_buffer``.  Unless disabled, each record carries three
    wall-clock stamps: ``wall_s`` (total elapsed), ``compile_s`` (XLA
    compilation incurred by this run so far, measured via
    :func:`timed_chunk_builder`), and the steady-state
    ``run_s = wall_s - compile_s`` — so rounds/s numbers derived from the
    history no longer fold first-chunk compilation in.  A repeat ``run``
    with the same builder reuses its compiled executables and stamps
    ``compile_s`` ≈ 0.

    ``telemetry`` (a ``repro.obs.events.Telemetry``, or anything with the
    same ``span``/``span_event`` surface; ``None`` means ``obs.NULL``) names
    the host phases of each chunk: ``engine.dispatch`` (the call that
    enqueues it), ``engine.readback`` (the metrics read-back and the
    ``wall_clock`` wait: where the host waits for the chunk) and
    ``engine.hooks``, plus an ``engine.compile`` span event whenever a
    chunk incurred XLA compilation.  Spans are profiler annotations; only
    with sinks do they also read the clock and emit events.  Neither
    changes the executed program.
    """
    chunk_rounds = max(int(chunk_rounds), 1)
    if hasattr(build_chunk, "stats"):
        build = build_chunk
    else:
        # memoize the wrapper on the builder: a second run() with the same
        # builder (checkpoint-restore resume, back-to-back benchmark runs)
        # must reuse the compiled executables, not AOT-compile afresh
        build = getattr(build_chunk, "_timed", None)
        if build is None:
            build = timed_chunk_builder(build_chunk)
            try:
                build_chunk._timed = build
            except AttributeError:
                pass
    if telemetry is None:
        telemetry = obs.NULL
    history: List[dict] = []
    start = int(state.round)
    final_round = jnp.int32(total_rounds - 1)
    t0 = time.perf_counter()
    compile_before = build.stats["compile_s"]
    r = start
    while r < total_rounds:
        length = min(chunk_rounds, total_rounds - r)
        if boundary_every:
            next_boundary = (r // boundary_every + 1) * boundary_every
            length = min(length, next_boundary - r)
        comp_prev = build.stats["compile_s"]
        with telemetry.span("engine.dispatch", round=r, length=length):
            state, buf = build(length)(state, final_round)
        comp_delta = build.stats["compile_s"] - comp_prev
        if comp_delta > 0:
            # compilation happens inside the first call at each length
            # (timed_chunk_builder's AOT path) — surface it as its own
            # span so dispatch time reads as steady-state
            telemetry.span_event("engine.compile", comp_delta,
                                 round=r, length=length)
        with telemetry.span("engine.readback", round=r):
            records = records_from_buffer(buf)
            if wall_clock:
                # the stamp covers the chunk's device work, not just its
                # enqueue
                jax.block_until_ready(state)
        if wall_clock:
            wall = time.perf_counter() - t0
            # only compilation incurred by THIS run: the builder (and its
            # stats) may be shared across runs, while t0 is per-run
            comp = build.stats["compile_s"] - compile_before
            for rec in records:
                # 3-decimal stamps: 1-decimal rounding collapsed sub-100ms
                # chunks to wall_s=0.0; run_s clamps at 0 because compile_s
                # is measured around the AOT build while wall spans this
                # run, so tiny first-chunk runs could go negative
                rec["wall_s"] = round(wall, 3)
                rec["compile_s"] = round(comp, 3)
                rec["run_s"] = round(max(wall - comp, 0.0), 3)
        history.extend(records)
        with telemetry.span("engine.hooks", round=r):
            for hook in hooks:
                hook(state, records, r)
        r += length
        if stop_fn is not None and stop_fn(records):
            break
    return state, history


def telemetry_hook(telemetry, *, ledger=None, health_fn=None,
                   health_every: int = 1) -> Hook:
    """Chunk-boundary telemetry: the sibling of :func:`checkpoint_hook`.

    Per boundary, emits into ``telemetry`` (``repro.obs.events.Telemetry``):

    * one ``metrics`` event per history record of the chunk (the streamed
      diagnostics rows, verbatim);
    * a ``ledger`` event when a ``repro.obs.ledger.CommLedger`` is given —
      the chunk's analytically-accounted communication plus running totals
      (``ledger.add_rounds`` is driven here, from ``state.round``);
    * the ``health_fn(state) -> {name: float}`` gauges (e.g.
      ``repro.obs.profiler.health_gauges``: Σc drift, consensus, EF residual
      norms), sampled every ``health_every``-th boundary.

    Everything is host-side.  ``health_fn`` is the only part that touches
    the device (a few tiny reductions + one small transfer per sample) —
    pass ``None`` to keep the run dispatch-identical to an untelemetered
    one; the hook itself never alters the trajectory either way.
    """
    state_holder = {"boundaries": 0}

    def hook(state, records, prev_round):
        for rec in records:
            telemetry.metrics(rec)
        if ledger is not None:
            rounds = int(state.round) - int(prev_round)
            if rounds > 0:
                ledger.add_rounds(rounds)
                telemetry.emit(ledger.event(rounds=rounds,
                                            round=int(state.round)))
        if health_fn is not None:
            b = state_holder["boundaries"]
            state_holder["boundaries"] = b + 1
            if b % max(int(health_every), 1) == 0:
                for name, value in health_fn(state).items():
                    telemetry.gauge(name, value, round=int(state.round))

    return hook


def checkpoint_hook(directory: str, every: int, metadata: Optional[dict] = None,
                    verbose: bool = False) -> Hook:
    """Chunk-boundary checkpointing: saves when the boundary crosses a
    multiple of ``every`` rounds (with the engine, checkpoints land on chunk
    boundaries — ``state.round`` in the filename/metadata keeps the resume
    point exact regardless of alignment).  A boundary can cross several
    multiples at once; pass ``boundary_every=every`` to ``run`` to split
    chunks at the exact multiples (``launch/train`` does)."""
    from repro.checkpoint import checkpoint as ckpt_lib

    def hook(state, records, prev_round):
        r = int(state.round)
        if not every or r // every <= prev_round // every:
            return
        path = os.path.join(directory, f"round_{r:06d}.npz")
        meta = dict(metadata or {})
        meta["round"] = r
        ckpt_lib.save(path, state, metadata=meta)
        if verbose:
            print(f"[engine] checkpoint -> {path}", flush=True)

    return hook
