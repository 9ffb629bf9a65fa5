"""Device time by named scope, on a trace recorded here on the CPU and on
intervals whose answers are known."""
import re
import time

import jax
import jax.numpy as jnp
import pytest

import scopes
import trace_reader as tr

#: The CPU backend runs a program's ops on its own threads, one host plane.
CPU_PLANE = re.compile(r"^/host:CPU$")


def _program(a, b):
    with jax.named_scope("kgt.grads"):
        g = jax.grad(lambda a: jnp.sum(jnp.tanh(a @ b)))(a)
    with jax.named_scope("kgt.epilogue"):
        a = a - 0.1 * g
    return a @ b + jnp.sin(a)   # the sine and the last product: no scope


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A window with three calls of a scoped program, each in engine spans
    inside a harness span, then 20 ms of host work in ``engine.hooks``."""
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(_program)
    a, b = jnp.ones((256, 256)), jnp.full((256, 256), 0.01)
    f(a, b).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train.chunk"):
                with jax.profiler.TraceAnnotation("engine.dispatch"):
                    out = f(a, b)
                with jax.profiler.TraceAnnotation("engine.readback"):
                    out.block_until_ready()
                with jax.profiler.TraceAnnotation("engine.hooks"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    path = tr.latest_xplane(d)
    with open(path, "rb") as fh:
        xspace = fh.read()
    return tr.load(path), xspace


def test_programs_read_the_op_names_of_the_trace(recorded):
    _, xspace = recorded
    names = [n for prog in scopes.programs(xspace).values()
             for n in prog.values()]
    assert any("/kgt.grads/transpose(" in n for n in names)
    assert any("/kgt.epilogue/" in n for n in names)


def test_scope_seconds_add_up_to_the_busy_time(recorded):
    profile, xspace = recorded
    layered = scopes.layered_ops(profile, xspace, plane=CPU_PLANE,
                                 line_name=None)
    trace = tr.Trace(layered, tr.host_spans(profile))
    per = scopes.exclusive_ns(layered[0], trace.lo, trace.hi)
    for layer in ("forward", "backward", "epilogue", "unscoped"):
        assert per.get(layer, 0.0) > 0, layer
    assert set(per) <= set(scopes.LAYERS)
    assert sum(per.values()) == pytest.approx(
        tr.busy_ns(layered[0], trace.lo, trace.hi))
    ms = scopes.round_ms(layered, trace.lo, trace.hi, rounds=3)
    assert sum(ms.values()) == pytest.approx(trace.busy_s() * 1e3 / 3)


def test_engine_spans_leave_the_window_and_busy_time_alone(recorded):
    """The window still comes from ``bench.window`` and the busy time and
    idle share read the same intervals; only the gap labels move, to the
    innermost span, here the engine's."""
    profile, xspace = recorded
    layered = scopes.layered_ops(profile, xspace, plane=CPU_PLANE,
                                 line_name=None)
    ops = {0: [(s, e, "op") for s, e, _ in layered[0]]}
    bench_only = tr.Trace(ops, tr.host_spans(profile))
    both = tr.Trace(ops, scopes.host_spans(profile))
    assert (both.lo, both.hi) == (bench_only.lo, bench_only.hi)
    assert both.busy_s() == bench_only.busy_s()
    assert both.idle_share() == bench_only.idle_share()
    names = {s[2] for s in both.spans}
    assert {"engine.dispatch", "engine.readback", "engine.hooks",
            "bench.train.chunk", "bench.window"} <= names
    label, secs = both.breakdown()["idle_gaps"][0]
    assert label == "engine.hooks" and secs >= 0.015
    assert bench_only.breakdown()["idle_gaps"][0][0] == "bench.train.chunk"


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str as a
    length-delimited value."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, op_name=None, called=()):
    out = _field(1, name) + _field(35, iid)
    if op_name is not None:
        out += _field(7, _field(2, op_name))
    if called:
        out += _field(38, b"".join(_varint(c) for c in called))
    return out


def _comp(cid, root, *instrs):
    return (_field(5, cid) + _field(6, root)
            + b"".join(_field(2, i) for i in instrs))


def test_an_instruction_without_op_name_takes_its_called_computations():
    """XLA makes some fusions without metadata (a convert it moved): they
    take their fused root's op_name, else the last fused instruction's."""
    body = "jit(chunk_step)/while/body/"
    module = b"".join(_field(3, c) for c in (
        _comp(1, 11, _instr(10, "param_0"), _instr(
            12, "bitcast.1", body + "kgt.grads/vmap(jvp())/transpose"),
            _instr(11, "convert.1")),
        _comp(2, 21, _instr(20, "param_0.1"),
              _instr(21, "add.3", body + "kgt.epilogue/add")),
        _comp(3, 33,
              _instr(30, "bitcast_convert_fusion.18", called=[1]),
              _instr(31, "fusion.7", called=[2]),
              _instr(32, "fusion.8", body + "engine.sampler/x", called=[2]),
              _instr(33, "copy.5"))))
    event_md = _field(1, 77) + _field(5, _field(1, 1) + _field(
        6, _field(1, module)))
    plane = (_field(2, "/host:metadata")
             + _field(4, _field(1, 77) + _field(2, event_md))
             + _field(5, _field(1, 1) + _field(2, _field(1, 1) + _field(
                 2, scopes.HLO_PROTO_STAT))))
    xspace = _field(1, _field(2, "/host:CPU")) + _field(1, plane)
    names = scopes.programs(xspace)[77]
    layers = {n: scopes.layer_of(n, names.get(n)) for n in (
        "bitcast_convert_fusion.18", "fusion.7", "fusion.8", "copy.5")}
    assert layers == {"bitcast_convert_fusion.18": "forward",
                      "fusion.7": "epilogue", "fusion.8": "sampler",
                      "copy.5": "unscoped"}


def test_layer_rules():
    body = "jit(chunk_step)/while/body/"
    assert scopes.layer_of(
        "fusion.3", body + "kgt.grads/transpose(jvp(dot_general))/dot_general"
    ) == "backward"
    assert scopes.layer_of(
        "fusion.1049.remat4", body + "kgt.grads/jvp(tanh)/tanh") == "backward"
    assert scopes.layer_of(
        "fusion.12", body + "kgt.grads/jvp(tanh)/tanh") == "forward"
    assert scopes.layer_of("add.4", body + "kgt.local_update/add") \
        == "local_update"
    assert scopes.layer_of("fusion.7", body + "kgt.epilogue/dot_general") \
        == "epilogue"
    assert scopes.layer_of("iota_reduce_fusion.5",
                           body + "engine.sampler/argmax") == "sampler"
    assert scopes.layer_of("fusion.9", body + "engine.metrics/cond/exp") \
        == "metrics"
    assert scopes.layer_of("copy.1", None) == "unscoped"
    assert scopes.layer_of("copy.2", body + "add") == "unscoped"
    # a name that only begins like a scope is no scope
    assert scopes.layer_of("fusion.8", body + "kgt.gradsx/add") == "unscoped"


def test_exclusive_time_of_overlapping_ops():
    ops = [(0, 10, "a"), (2, 4, "b"), (8, 12, "c"), (20, 25, "d")]
    per = scopes.exclusive_ns(ops, 0, 30)
    # each instant goes to the latest started op running then
    assert per == {"a": 6, "b": 2, "c": 4, "d": 5}
    assert sum(per.values()) == tr.busy_ns(ops, 0, 30)
    # in [9, 22] a and c start together: the shorter goes first
    assert scopes.exclusive_ns(ops, 9, 22) == {"a": 1, "c": 2, "d": 2}


def test_round_ms_and_the_unscoped_remainder():
    layered = {0: [(0.0, 4e6, "forward"), (4e6, 10e6, "backward"),
                   (10e6, 11e6, "unscoped")],
               1: [(0.0, 6e6, "forward"), (6e6, 12e6, "epilogue")]}
    ms = scopes.round_ms(layered, 0.0, 20e6, rounds=2)
    assert ms["forward"] == pytest.approx((4 + 6) / 2 / 2)
    assert ms["backward"] == pytest.approx(6 / 2 / 2)
    assert ms["epilogue"] == pytest.approx(6 / 2 / 2)
    assert ms["unscoped"] == pytest.approx(1 / 2 / 2)
    assert ms["sampler"] == ms["metrics"] == ms["local_update"] == 0.0
    assert sum(ms.values()) == pytest.approx((11 + 12) / 2 / 2)
    # a program without scopes reads nothing, and so does an empty window
    assert scopes.round_ms({0: [(0.0, 5e6, "unscoped")]}, 0, 1e7, 1) is None
    assert scopes.round_ms(layered, 30e6, 40e6, rounds=2) is None


def test_gap_labels_prefer_the_engine_span_inside_the_harness_span():
    spans = sorted([(0, 100, "bench.train.chunk"), (40, 60, "engine.hooks"),
                    (60, 70, "engine.dispatch"), (0, 200, "bench.window")])
    gaps = [(45, 55), (10, 12), (62, 69), (150, 160)]
    labels = tr.label_gaps(gaps, spans)
    assert labels[0][0] == "engine.hooks"
    assert [name for name, _ in labels] == [
        "engine.hooks", "bench.window", "engine.dispatch",
        "bench.train.chunk"]
