"""The trace reduction, on a small trace recorded here on the CPU and on
intervals whose answers are known."""
import time

import jax
import jax.numpy as jnp
import pytest

import trace_reader as tr


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A window with three matmul calls 20 ms apart, each in a span."""
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((256, 256))
    f(a).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(a).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    return tr.load(tr.latest_xplane(d))


def _cpu_ops(profile):
    """The CPU backend's XLA op events stand in for a chip's ops."""
    ops = []
    for plane in profile.planes:
        for line in plane.lines:
            for s, e, text in tr.events(line):
                name = tr.op_name(text)
                if name.startswith("dot") or name.startswith("wrapped_"):
                    ops.append((s, e, name))
    return ops


def test_spans_and_window_from_a_recorded_trace(cpu_trace):
    spans = tr.host_spans(cpu_trace)
    names = [s[2] for s in spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.call") == 3
    trace = tr.Trace({0: _cpu_ops(cpu_trace)}, spans)
    assert trace.window_s >= 0.06  # three 20 ms sleeps
    calls = [s for s in spans if s[2] == "bench.call"]
    assert all(trace.lo <= s[0] <= s[1] <= trace.hi for s in calls)


def test_idle_union_on_a_recorded_trace(cpu_trace):
    ops = _cpu_ops(cpu_trace)
    assert ops, "the CPU trace holds no XLA op events"
    trace = tr.Trace({0: ops}, tr.host_spans(cpu_trace))
    merged = tr.union(tr.clip(ops, trace.lo, trace.hi))
    for (s0, e0), (s1, _) in zip(merged, merged[1:]):
        assert e0 < s1
    busy = sum(e - s for s, e in merged) * 1e-9
    assert trace.busy_s() == pytest.approx(busy)
    gap_total = sum(e - s for s, e in tr.gaps(ops, trace.lo, trace.hi))
    assert (gap_total * 1e-9 + busy) == pytest.approx(trace.window_s)
    # the sleeps between the calls are idle, and the longest gaps fall in
    # the window's own span, not in a call
    assert trace.idle_share() > 0.5
    labels = trace.breakdown()["idle_gaps"]
    assert labels[0][0] == "bench.window" and labels[0][1] >= 0.015


def test_kernel_lookup_by_name(cpu_trace):
    ops = _cpu_ops(cpu_trace)
    secs, calls = tr.op_time_ns(ops, r"^dot")
    assert calls >= 3 and secs > 0
    assert tr.op_time_ns(ops, r"^fused_round(\.\d+)?$") == (0.0, 0)
    tpu_text = ("%fused_round.9 = (f32[8,128]{1,0:T(8,128)}, f32[8,128]) "
                "custom-call(f32[8,8]{1,0} %w, f32[8,128]{1,0} %z)")
    assert tr.op_name(tpu_text) == "fused_round.9"
    ops = [(0.0, 5.0, tr.op_name(tpu_text)), (5.0, 6.0, "fusion.3"),
           (7.0, 9.0, "fused_round.9")]
    assert tr.op_time_ns(ops, r"^fused_round(\.\d+)?$") == (7.0, 2)


def test_idle_union_of_nested_and_overlapping_ops():
    ops = [(0, 10, "a"), (2, 4, "b"), (8, 12, "c"), (20, 25, "d")]
    assert tr.busy_ns(ops, 0, 30) == 17
    assert tr.gaps(ops, 0, 30) == [(12, 20), (25, 30)]
    assert tr.busy_ns(ops, 9, 22) == 5


def test_exposed_collective_overlap():
    ops = [(0, 10, "fusion.1"), (5, 15, "all-gather.2"), (20, 30,
                                                           "all-reduce.1"),
           (22, 24, "fusion.2")]
    # 10-15 and 20-22, 24-30 are collective time with no compute
    assert tr.exposed_collective_ns(ops, 0, 40) == 13
    # an all-gather in flight on the asynchronous line counts too
    in_flight = [(32, 36, "all-gather-start.1"), (30, 34, "copy-start.2")]
    assert tr.exposed_collective_ns(ops, 0, 40, in_flight) == 17
    trace = tr.Trace({0: ops, 1: [(0, 40, "fusion.9")]}, [],
                     window=(0, 40), in_flight={0: in_flight})
    assert trace.exposed_collective_s() == pytest.approx(17 / 2 * 1e-9)


def test_control_flow_ops_do_not_count_as_busy():
    assert tr.CONTAINER.match("while.605")
    assert not tr.CONTAINER.match("fusion.1049.remat4")
    assert tr.COLLECTIVE.match("all-gather-start.3")
    assert not tr.COLLECTIVE.match("fusion.all-gather")
