"""Opt-in profiler capture + algorithm-health gauges.

:class:`Profiler` wraps ``jax.profiler``'s Perfetto trace capture behind an
N-round window: ``start()`` before ``engine.run`` opens the trace, and the
profiler's chunk-boundary hook closes it once the requested number of
rounds has executed (0 = the whole run, closed by ``stop()``/context exit).
The trace lands under ``directory`` and opens in Perfetto / TensorBoard.
It records the device ops, whose ``op_name`` carries the program's named
scopes, and the engine's spans on the same clock; Python calls are not
traced.

:func:`health_gauges` samples the algorithm-health quantities the theory
says to watch — host-side, from the state at a chunk boundary, so they cost
a handful of tiny reductions **only when telemetry is on**:

* ``corr_x_drift`` / ``corr_y_drift`` — ‖c̄‖ for both corrections (Lemma 8
  says exactly 0 for the tracking variants; drift means the correction
  update is wrong);
* ``consensus_x`` / ``consensus_y`` — the client-variance consensus errors
  Ξx/Ξy;
* ``ef_x_norm`` / ``ef_y_norm`` — error-feedback residual norms (present
  only under ``gossip_compress``; a growing residual means the quantizer is
  systematically starved).

Byzantine configuration (attacker count/model) is static per run and is
stamped into the run's ``meta`` event by the caller (``launch/train``), not
sampled here.
"""
from __future__ import annotations

from typing import Optional


def health_gauges(state) -> dict:
    """Algorithm-health gauges from a ``KGTState`` (host floats)."""
    import jax.numpy as jnp

    from repro.core import kgt_minimax as kgt
    from repro.core import mixing as mixing_lib

    out = {
        "corr_x_drift": float(kgt.correction_mean_norm(state.cx)),
        "corr_y_drift": float(kgt.correction_mean_norm(state.cy)),
        "consensus_x": float(mixing_lib.consensus_error(state.x)),
        "consensus_y": float(mixing_lib.consensus_error(state.y)),
    }
    for name in ("ef_x", "ef_y"):
        buf = getattr(state, name, None)
        if buf is not None:
            out[f"{name}_norm"] = float(
                jnp.sqrt(jnp.sum(jnp.square(buf.astype(jnp.float32)))))
    return out


class Profiler:
    """An N-round ``jax.profiler`` capture window.

    >>> prof = Profiler("/tmp/trace", num_rounds=8)
    >>> prof.start()                       # before engine.run
    >>> hooks.append(prof.hook)            # closes after 8 rounds
    >>> ...
    >>> prof.stop()                        # idempotent backstop

    ``num_rounds=0`` captures the whole run.  Failures to start/stop (no
    profiler backend in exotic builds) are swallowed after a one-line
    warning — profiling must never take a training run down.
    """

    def __init__(self, directory: str, num_rounds: int = 0) -> None:
        self.directory = directory
        self.num_rounds = int(num_rounds)
        self.active = False
        self._stop_round: Optional[int] = None

    def start(self) -> None:
        if self.active:
            return
        try:
            import jax.profiler

            opts = jax.profiler.ProfileOptions()
            # device ops and the engine's spans only: tracing every Python
            # call would widen every host gap the trace is read for
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.directory, profiler_options=opts)
            self.active = True
        except Exception as e:  # noqa: BLE001 — never take the run down
            print(f"[obs] profiler start failed: {e!r}", flush=True)

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        try:
            import jax.profiler

            jax.profiler.stop_trace()
            print(f"[obs] profiler trace -> {self.directory}", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"[obs] profiler stop failed: {e!r}", flush=True)

    def hook(self, state, records, prev_round) -> None:
        """Engine chunk-boundary hook: close the window once ``num_rounds``
        rounds have run since capture started."""
        if not self.active or not self.num_rounds:
            return
        if self._stop_round is None:
            # first boundary after start(): the window began at prev_round
            self._stop_round = int(prev_round) + self.num_rounds
        if int(state.round) >= self._stop_round:
            self.stop()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
