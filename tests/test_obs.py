"""Tests of the observability subsystem (repro.obs).

The load-bearing claims: telemetry off is *zero-overhead* (bit-identical
trajectories, no extra dispatches; a sink-less span is only a profiler
annotation and reads no clock); the program's named scopes and the engine's
spans reach the profiler's trace; the communication ledger's analytic
bytes/round match hand-computed wire arithmetic for every lowering family
and separate the lowerings in the expected ratios; and a JSONL artifact
round-trips through ``repro.obs.report`` for every event type.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as engine_lib
from repro import obs
from repro.configs.base import AlgorithmConfig
from repro.core import (
    init_state,
    make_quadratic_data,
    make_round_step,
    quadratic_problem,
)
from repro.obs import report


def _setup(algo="kgt_minimax", mixing_impl="dense", n=4, K=3, sigma=0.3,
           seed=0, gossip_compress=None):
    key = jax.random.PRNGKey(seed)
    data = make_quadratic_data(key, n, dx=6, dy=3, heterogeneity=1.5)
    prob = quadratic_problem(data, sigma=sigma)
    cfg = AlgorithmConfig(
        algorithm=algo, num_clients=n, local_steps=K, eta_cx=0.01,
        eta_cy=0.1, eta_sx=0.5, eta_sy=0.5, topology="ring",
        mixing_impl=mixing_impl, gossip_backend="xla",
        gossip_compress=gossip_compress)
    cb = {k: v for k, v in data.items() if k != "mu"}
    kb = jax.tree.map(lambda v: jnp.broadcast_to(v[None], (K, *v.shape)), cb)
    st = init_state(prob, cfg, key, init_batch=cb,
                    init_keys=jax.random.split(key, n))
    step = make_round_step(prob, cfg)
    sampler = engine_lib.make_fixed_batch_sampler(
        kb, local_steps=K, num_clients=n, seed=seed)
    return prob, cfg, st, step, sampler


def _assert_states_equal(a, b, context=""):
    for name in ("x", "y", "cx", "cy"):
        for la, lb in zip(jax.tree.leaves(getattr(a, name)),
                          jax.tree.leaves(getattr(b, name))):
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb), err_msg=f"{context}:{name}")
    assert int(a.round) == int(b.round)


# ---------------------------------------------------------------- events


def test_disabled_telemetry_is_noop(monkeypatch):
    """A sink-less Telemetry touches no sink and reads no Python clock: a
    span only opens the profiler annotation of its fixed name (a no-op
    unless a trace is capturing), and emit/metrics return before stamping."""
    from repro.obs import events

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"a disabled telemetry read time.{name}")

    monkeypatch.setattr(events, "time", NoClock())
    tel = obs.Telemetry(())
    assert not tel.enabled
    for t in (tel, obs.NULL):
        span = t.span("engine.readback", round=3)
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            pass
    tel.span_event("engine.compile", 1.0)
    tel.metrics({"round": 0})
    tel.counter("bytes", 10)
    tel.gauge("g", 1.0)
    tel.close()
    assert tel.sinks == [] and obs.NULL.sinks == []


def test_telemetry_stamps_and_fans_out():
    a, b = obs.MemorySink(), obs.MemorySink()
    tel = obs.Telemetry([a, b])
    with tel.span("engine.dispatch", round=2, length=4):
        pass
    tel.counter("rounds", 4)
    tel.gauge("consensus_x", 0.5, round=4)
    tel.metrics({"round": 3, "f_bar": 1.25})
    tel.meta("run", arch="toy")
    assert len(a.events) == len(b.events) == 5
    for ev in a.events:
        assert ev["v"] == obs.TELEMETRY_VERSION
        assert ev["type"] in ("span", "counter", "gauge", "metrics", "meta")
        assert "t" in ev
    span = a.events[0]
    assert span["name"] == "engine.dispatch" and span["dur_s"] >= 0
    assert span["round"] == 2 and span["length"] == 4
    assert a.events[3]["f_bar"] == 1.25


def test_stderr_sink_formatter_filters(capsys):
    """formatter -> None drops the event from the console entirely."""
    sink = obs.StderrSink(lambda ev: f"row {ev['round']}"
                          if ev["type"] == "metrics" else None)
    tel = obs.Telemetry([sink])
    tel.metrics({"round": 7})
    tel.gauge("hidden", 1.0)
    err = capsys.readouterr().err
    assert "row 7" in err and "hidden" not in err


# ------------------------------------------------------- zero overhead


def test_engine_bit_identical_with_telemetry_on():
    """The hard guarantee: running the engine with a full telemetry stack
    (spans + metrics/ledger/health hook) produces the bit-identical state
    and history to the plain telemetry=None run."""
    prob, cfg, st, step, sampler = _setup()
    build = engine_lib.make_chunk_builder(
        step, sampler, engine_lib.quadratic_metrics_fn(prob), log_every=2,
        donate=False)
    st_plain, hist_plain = engine_lib.run(
        st, build, total_rounds=10, chunk_rounds=4, wall_clock=False)

    sink = obs.MemorySink()
    tel = obs.Telemetry([sink])
    ledger = obs.ledger_for_state(cfg, st)
    hook = engine_lib.telemetry_hook(tel, ledger=ledger,
                                     health_fn=obs.health_gauges)
    st_tel, hist_tel = engine_lib.run(
        st, build, total_rounds=10, chunk_rounds=4, wall_clock=False,
        hooks=[hook], telemetry=tel)

    _assert_states_equal(st_plain, st_tel, "telemetry on/off")
    assert hist_plain == hist_tel
    # and the stream actually recorded the run
    types = {ev["type"] for ev in sink.events}
    assert {"span", "metrics", "ledger", "gauge"} <= types
    assert ledger.rounds == 10


ENGINE_SPANS = ("engine.dispatch", "engine.readback", "engine.hooks",
                "engine.compile")
#: The named scopes of the chunk program (docs/architecture.md).
SCOPES = ("engine.sampler", "kgt.grads", "kgt.local_update", "kgt.epilogue",
          "engine.metrics")


def _trace_span_counts(directory):
    """{span name: count} of the engine's annotations on the host planes of
    the one trace under ``directory``."""
    import glob
    import os

    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    counts = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    return counts


@pytest.mark.parametrize("with_sinks", [False, True])
def test_engine_spans_reach_the_profiler_trace(tmp_path, with_sinks):
    """Each chunk records engine.dispatch, engine.readback and engine.hooks
    once on the profiler's clock, with sinks or without; engine.compile is
    recorded on the first call only, in the trace and, with sinks, as one
    span event under the same name."""
    prob, cfg, st, step, sampler = _setup()
    build = engine_lib.make_chunk_builder(
        step, sampler, engine_lib.quadratic_metrics_fn(prob), log_every=2,
        donate=False)
    sink = obs.MemorySink()
    tel = obs.Telemetry([sink]) if with_sinks else None
    seen = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        st, _ = engine_lib.run(st, build, total_rounds=8, chunk_rounds=4,
                               hooks=[lambda *a: seen.append(a[2])],
                               telemetry=tel)
        # a second run on the same builder reuses the compiled chunk
        engine_lib.run(st, build, total_rounds=16, chunk_rounds=4,
                       hooks=[lambda *a: seen.append(a[2])], telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    assert seen == [0, 4, 8, 12]
    counts = _trace_span_counts(str(tmp_path))
    assert counts == {"engine.dispatch": 4, "engine.readback": 4,
                      "engine.hooks": 4, "engine.compile": 1}
    events = [(e["name"], e.get("round")) for e in sink.events
              if e["type"] == "span"]
    if with_sinks:
        assert sorted(set(n for n, _ in events)) == sorted(ENGINE_SPANS)
        assert [r for n, r in events if n == "engine.compile"] == [0]
        assert [r for n, r in events if n == "engine.hooks"] == [0, 4, 8, 12]
    else:
        assert events == []


def test_chunk_program_carries_the_named_scopes(monkeypatch):
    """The compiled chunk program of --reduced paper-toy has instructions
    under each of the five scopes, and backward ones (``transpose(``)
    under kgt.grads: what a device trace's ops are attributed by."""
    import re

    from repro.launch import train as train_lib

    texts = []
    original = jax.stages.Lowered.compile

    def compile_and_keep(lowered, *a, **kw):
        compiled = original(lowered, *a, **kw)
        texts.append(compiled.as_text())
        return compiled

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_and_keep)
    args = train_lib.build_parser().parse_args([
        "--arch", "paper-toy", "--reduced", "--clients", "2",
        "--local-steps", "2", "--batch", "2", "--seq-len", "32",
        "--rounds", "2", "--chunk", "2", "--log-every", "2",
        "--engine", "scan", "--mesh", "host", "--mixing-impl", "dense"])
    train_lib.train(args)
    (text,) = [t for t in texts if t.startswith("HloModule jit_chunk_step")]
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in op_names), scope
    grads = [n for n in op_names if "/kgt.grads/" in n]
    assert any("transpose(" in n for n in grads)
    assert any("transpose(" not in n for n in grads)


def test_telemetry_hook_emits_per_boundary():
    sink = obs.MemorySink()
    tel = obs.Telemetry([sink])
    comm = obs.round_comm(mixing_impl="dense", n=4, dims=(6, 3))
    ledger = obs.CommLedger(comm)
    calls = []

    def health(state):
        calls.append(int(state.round))
        return {"corr_x_drift": 0.0}

    hook = engine_lib.telemetry_hook(tel, ledger=ledger, health_fn=health,
                                     health_every=2)

    class S:
        def __init__(self, r):
            self.round = jnp.int32(r)

    hook(S(4), [{"round": 1}, {"round": 3}], 0)
    hook(S(8), [{"round": 5}], 4)
    hook(S(12), [], 8)
    metrics = [e for e in sink.events if e["type"] == "metrics"]
    ledgers = [e for e in sink.events if e["type"] == "ledger"]
    gauges = [e for e in sink.events if e["type"] == "gauge"]
    assert [m["round"] for m in metrics] == [1, 3, 5]
    assert [l["rounds"] for l in ledgers] == [4, 4, 4]
    assert ledgers[-1]["rounds_total"] == 12
    assert ledgers[-1]["bytes_total"] == 12 * comm.bytes_per_round
    # health_every=2: boundaries 0 and 2 sample, boundary 1 skips
    assert calls == [4, 12]
    assert all(g["name"] == "corr_x_drift" for g in gauges)


# ------------------------------------------------------------- ledger


def test_ledger_dense_hand_computed():
    """n=8, dims (10, 5), f32, tracking: every client receives from the
    other 7 -> 56 links, two gossiped quantities (Δ and θ) of 15 elements
    at 4 bytes."""
    c = obs.round_comm(mixing_impl="dense", n=8, dims=(10, 5))
    assert c.links == 8 * 7
    assert c.quantities == 2
    assert c.bytes_per_round == 56 * 15 * 4 * 2 == 6720
    assert c.collectives_per_round == 4        # 2 per leaf x (1, 1) leaves


def test_ledger_separates_lowerings_in_expected_ratios():
    """The acceptance criterion: dense vs sparse_packed vs
    fused_round+int8 differ in analytically expected ratios."""
    n, dims = 8, (10, 5)
    dense = obs.round_comm(mixing_impl="dense", n=n, dims=dims)
    sparse = obs.round_comm(mixing_impl="sparse_packed", n=n, dims=dims,
                            topology="ring")
    fused8 = obs.round_comm(mixing_impl="fused_round", n=n, dims=dims,
                            gossip_compress="int8")

    # sparse ring support: 2 neighbors/client -> 16 directed edges; the
    # bytes ratio vs all-gather dense is exactly (n-1)/deg = 7/2
    assert sparse.links == 2 * n
    assert dense.bytes_per_round / sparse.bytes_per_round == (n - 1) / 2
    assert sparse.bytes_per_round == 16 * 15 * 4 * 2 == 1920

    # int8 narrows the Δ-gossip to 1 B/elem + one f32 scale per variable
    # per link; θ stays f32
    theta = 56 * 15 * 4
    delta = 56 * (15 * 1 + 4 * 2)
    assert fused8.bytes_per_round == theta + delta == 4648
    assert fused8.bytes_per_round / dense.bytes_per_round == pytest.approx(
        (theta + delta) / 6720)

    # three distinct lowerings -> three distinct bytes/round
    assert len({dense.bytes_per_round, sparse.bytes_per_round,
                fused8.bytes_per_round}) == 3
    # and the collective-launch progression of the gossip bench: 4 -> 2 -> 1
    assert dense.collectives_per_round == 4
    assert sparse.collectives_per_round == 2
    assert fused8.collectives_per_round == 1


def test_ledger_ring_and_edge_cases():
    ring = obs.round_comm(mixing_impl="ring", n=8, dims=(10, 5))
    assert ring.links == 16
    assert ring.bytes_per_round == 16 * 15 * 4 * 2
    assert obs.links_per_gossip("ring", 2) == 2    # one neighbor each
    assert obs.links_per_gossip("ring", 1) == 0
    # bf16 compression: 2 B/elem on the Δ wire, no row scale
    bf = obs.round_comm(mixing_impl="pallas_packed", n=8, dims=(10, 5),
                        gossip_compress="bf16")
    assert bf.bytes_per_round == 56 * 15 * 4 + 56 * 15 * 2
    # no tracking on a packed lowering: single pre-stepped gossip
    nt = obs.round_comm(mixing_impl="pallas_packed", n=8, dims=(10, 5),
                        track=False)
    assert nt.quantities == 1
    assert nt.bytes_per_round == 56 * 15 * 4
    with pytest.raises(ValueError):
        obs.round_comm(mixing_impl="nope", n=8, dims=(10, 5))
    with pytest.raises(ValueError):
        obs.round_comm(mixing_impl="dense", n=8, dims=(10, 5),
                       gossip_compress="int3")


def test_ledger_for_state_reads_packed_dims():
    """ledger_for_state derives (D_x, D_y) from the live state's pack
    specs — the quadratic state is (n, 6) + (n, 3)."""
    prob, cfg, st, step, sampler = _setup(n=4)
    ledger = obs.ledger_for_state(cfg, st)
    assert ledger.comm.dims == (6, 3)
    assert ledger.comm.links == 4 * 3
    assert ledger.bytes_per_round == 12 * 9 * 4 * 2
    ledger.add_rounds(5)
    ev = ledger.event(rounds=5)
    assert ev["bytes_total"] == 5 * ledger.bytes_per_round
    assert ev["bytes"] == ev["bytes_total"]
    assert ev["type"] == "ledger"


def test_ledger_no_tracking_baseline_state():
    """local_sgda carries no corrections: packed lowerings collapse to one
    gossiped quantity."""
    prob, cfg, st, step, sampler = _setup(algo="local_sgda",
                                          mixing_impl="pallas_packed")
    ledger = obs.ledger_for_state(cfg, st)
    assert ledger.comm.quantities == 1


def test_sweep_cell_comm_matches_ledger():
    """sweep.run.cell_comm prices a cell point on the sweep geometry
    (DX=10, DY=5) with the point's own statics."""
    from repro.sweep import run as sweep_run

    c = sweep_run.cell_comm({"mixing_impl": "dense"})
    assert c.bytes_per_round == obs.round_comm(
        mixing_impl="dense", n=8, dims=(10, 5)).bytes_per_round
    c2 = sweep_run.cell_comm({"mixing_impl": "sparse_packed",
                              "algorithm": "local_sgda"})
    assert c2.quantities == 1


# ---------------------------------------------------- report round-trip


def test_jsonl_roundtrip_every_event_type(tmp_path):
    """Write one of every event type through the JsonlSink, fold it back
    through report.load + summarize."""
    path = str(tmp_path / "run.jsonl")
    tel = obs.Telemetry([obs.JsonlSink(path)])
    tel.meta("train", arch="toy", n=4)
    tel.span_event("engine.compile", 1.5, round=0)
    with tel.span("engine.dispatch", round=0, length=4):
        pass
    tel.counter("chunks", 1)
    tel.gauge("consensus_x", 0.25, round=4)
    tel.metrics({"round": 0, "phi_grad_norm": 2.0, "wall_s": 0.5})
    tel.metrics({"round": 4, "phi_grad_norm": 1.0, "wall_s": 1.0,
                 "run_s": 0.8, "compile_s": 1.5})
    ledger = obs.CommLedger(obs.round_comm(mixing_impl="dense", n=4,
                                           dims=(6, 3)))
    ledger.add_rounds(5)
    tel.emit(ledger.event(rounds=5))
    tel.close()

    events = report.load(path)
    assert {e["type"] for e in events} == set(obs.EVENT_TYPES)
    # jax scalars went through the float() fallback -> plain JSON numbers
    assert all(isinstance(e["t"], float) for e in events)
    s = report.summarize(events)
    assert s["num_events"] == 8
    assert s["spans"]["engine.compile"] == {"count": 1, "total_s": 1.5}
    assert s["spans"]["engine.dispatch"]["count"] == 1
    assert s["counters"]["chunks"] == {"count": 1, "sum": 1.0}
    assert s["gauges"]["consensus_x"] == 0.25
    assert s["meta"]["arch"] == "toy"
    assert s["rounds"] == 5 and s["num_metric_rows"] == 2
    assert s["rounds_per_s"] == pytest.approx(5 / 0.8, abs=1e-3)
    assert s["tail"] == {"phi_grad_norm": 1.0}
    assert s["ledger"]["bytes_per_round"] == ledger.bytes_per_round
    assert s["ledger"]["bytes_total"] == 5 * ledger.bytes_per_round
    rendered = report.render(s)
    assert "time breakdown" in rendered and "communication [dense]" in rendered


def test_jsonl_sink_never_raises_on_exotic_values(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = obs.Telemetry([obs.JsonlSink(path)])
    tel.metrics({"round": 0, "f_bar": jnp.float32(1.5),
                 "arr": np.arange(2), "obj": object()})
    tel.close()
    (ev,) = report.load(path)
    assert ev["f_bar"] == 1.5


def test_report_cli_fails_on_bad_artifacts(tmp_path, capsys):
    missing = str(tmp_path / "nope.jsonl")
    assert report.main([missing]) == 1

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report.main([str(empty)]) == 1

    malformed = tmp_path / "bad.jsonl"
    malformed.write_text('{"type": "meta"}\n{broken\n')
    assert report.main([str(malformed)]) == 1
    assert "bad.jsonl:2" in capsys.readouterr().err

    untyped = tmp_path / "untyped.jsonl"
    untyped.write_text('{"no_type": 1}\n')
    assert report.main([str(untyped)]) == 1

    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps({"type": "meta", "arch": "toy"}) + "\n")
    assert report.main([str(good)]) == 0
    assert report.main([str(good), "--json"]) == 0


# ----------------------------------------------------------- profiler


def test_profiler_window_closes_after_n_rounds():
    class Prof(obs.Profiler):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.stopped = 0

        def stop(self):
            self.stopped += 1
            self.active = False

    class S:
        def __init__(self, r):
            self.round = jnp.int32(r)

    prof = Prof("/tmp/unused", num_rounds=6)
    prof.active = True  # as if start_trace succeeded
    prof.hook(S(4), [], 0)     # window = rounds [0, 6)
    assert prof.active and prof.stopped == 0
    prof.hook(S(8), [], 4)
    assert prof.stopped == 1 and not prof.active
    prof.hook(S(12), [], 8)    # closed window: no double stop
    assert prof.stopped == 1

    whole = Prof("/tmp/unused", num_rounds=0)
    whole.active = True
    whole.hook(S(100), [], 96)  # 0 = whole run, only stop() closes it
    assert whole.active and whole.stopped == 0


def test_health_gauges_values():
    prob, cfg, st, step, sampler = _setup()
    g = obs.health_gauges(st)
    # tracking corrections start mean-zero by construction (Lemma 8), and
    # all clients share x0/y0 so consensus starts at 0
    assert g["corr_x_drift"] == pytest.approx(0.0, abs=1e-5)
    assert g["corr_y_drift"] == pytest.approx(0.0, abs=1e-5)
    assert g["consensus_x"] == pytest.approx(0.0, abs=1e-6)
    assert "ef_x_norm" not in g  # no compression -> no EF residuals
    for v in g.values():
        assert isinstance(v, float) and math.isfinite(v)


# -------------------------------------------------- train-driver wiring


def test_format_record_handles_sparse_schemas():
    """Satellite fix: _print_record used to KeyError on metric rows that
    lack f_bar/mean_loss/consensus_x (e.g. quadratic_metrics_fn rows)."""
    from repro.launch import train as train_lib

    quad_row = {"round": 3, "phi_grad_norm": 0.125, "wall_s": 1.5}
    line = train_lib._format_record(quad_row)
    assert "round    3" in line and "‖∇Φ‖=0.1250" in line

    dro_row = {"round": 2, "f_bar": 1.0, "mean_loss": 2.0, "eval_loss": 3.0,
               "consensus_x": 1e-4, "y_bar_norm": 0.5, "wall_s": 2.0}
    line = train_lib._format_record(dro_row)
    for frag in ("f(x̄,ȳ)=1.0000", "ℓ̄=2.0000", "Ξx=1.000e-04"):
        assert frag in line

    train_lib._print_record({"round": 0})  # must not raise on minimal rows
    assert train_lib._stderr_event_format({"type": "gauge"}) is None
    assert "‖∇Φ‖" in train_lib._stderr_event_format(
        {"type": "metrics", "v": 1, "t": 0.0, **quad_row})


def test_train_telemetry_artifact_and_zero_overhead(tmp_path):
    """End-to-end acceptance: --telemetry-out produces a JSONL that
    repro.obs.report folds (meta + spans + metrics + ledger + gauges), the
    ledger block matches the analytic model for the run's lowering, and
    the logged history is identical to the telemetry-off run."""
    from repro.launch import train as train_lib

    def args(**over):
        import argparse

        base = dict(
            arch="qwen2-0.5b", reduced=True, algorithm="kgt_minimax",
            rounds=4, clients=2, local_steps=2, batch=2, seq_len=32,
            groups=4, mu=1.0, alpha=0.3, eta_cx=0.02, eta_cy=0.2,
            eta_s=0.7, topology="ring", mixing_impl="dense",
            gossip_dtype="float32", schedule="constant", warmup=0, seed=0,
            log_every=2, checkpoint_every=0,
            checkpoint_dir=str(tmp_path / "ckpt"), out=None, engine="scan",
            chunk=2)
        base.update(over)
        return argparse.Namespace(**base)

    path = tmp_path / "run.jsonl"
    res_tel = train_lib.train(args(telemetry_out=str(path)))
    res_plain = train_lib.train(args())
    # identical up to the wall-clock stamps, which measure real time
    timing = ("wall_s", "compile_s", "run_s")
    strip = lambda hist: [{k: v for k, v in rec.items() if k not in timing}
                          for rec in hist]  # noqa: E731
    assert strip(res_tel["history"]) == strip(res_plain["history"])

    s = report.summarize(report.load(str(path)))
    assert s["meta"]["arch"].startswith("qwen2-0.5b")  # the reduced variant
    assert {"engine.dispatch", "engine.readback", "engine.hooks",
            "engine.compile"} <= set(s["spans"])
    assert s["num_metric_rows"] == len(res_tel["history"])
    assert {"corr_x_drift", "consensus_x"} <= set(s["gauges"])
    led = s["ledger"]
    assert led["mixing_impl"] == "dense" and led["rounds"] == 4
    # the analytic model for this run: n=2 dense all-gather
    assert led["bytes_per_round"] % (2 * 1 * 4) == 0
    assert led["bytes_total"] == 4 * led["bytes_per_round"]
    assert report.render(s)
