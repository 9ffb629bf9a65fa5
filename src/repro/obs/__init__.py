"""Structured telemetry: spans, counters/gauges, the communication ledger,
and opt-in profiler capture (see ``docs/architecture.md``, "Observability").

``events``   — the event model: ``Telemetry`` + pluggable sinks (memory,
               JSONL file, stderr).
``ledger``   — analytic per-round communication accounting (bytes on the
               wire + collective counts) per gossip lowering.
``profiler`` — ``jax.profiler`` Perfetto capture windows + algorithm-health
               gauges sampled at chunk boundaries.
``report``   — ``python -m repro.obs.report run.jsonl``: fold a run's JSONL
               into a time/communication/convergence summary.

Everything here is host-side and strictly opt-in: a run that does not
construct a sink dispatches nothing extra, its spans are only
``jax.profiler`` annotations (no-ops unless a trace is capturing), and its
trajectory is bit-identical to a run with sinks (tests/test_obs.py pins
that).
"""
from repro.obs.events import (  # noqa: F401
    EVENT_TYPES,
    NULL,
    TELEMETRY_VERSION,
    JsonlSink,
    MemorySink,
    StderrSink,
    Telemetry,
)
from repro.obs.ledger import (  # noqa: F401
    LEDGER_VERSION,
    CommLedger,
    RoundComm,
    ledger_for_state,
    links_per_gossip,
    round_comm,
)
from repro.obs.profiler import (  # noqa: F401
    Profiler,
    health_gauges,
)
