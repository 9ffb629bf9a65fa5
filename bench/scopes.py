"""Device time by the program's layers, read from its named scopes.

The program names its parts with ``jax.named_scope`` (docs/architecture.md,
"Observability"), and XLA keeps the scope in the ``op_name`` metadata of
every instruction it compiles from them:

================== ===========================================================
``engine.sampler``  the round's data, keys and extras drawn on the device
``kgt.grads``       forward and backward of each local step
``kgt.local_update`` the local SGDA update with the tracking correction
``kgt.epilogue``    Δ, the gossip, the correction and the parameter update
``engine.metrics``  the logged diagnostics and the held-out eval forward
================== ===========================================================

A trace holds the optimized HLO of every program it ran: its
``/host:metadata`` plane has one event metadata per program (the id is the
program id), whose ``Hlo Proto`` stat is the serialized ``HloProto``.  The
few fields read here are decoded from the protobuf wire format directly.
An op is matched to its instruction by program id and instruction name,
and gets the layer of its instruction's ``op_name`` (where XLA made the
instruction without one, that of the computation it calls).  ``kgt.grads``
splits into ``backward`` (``transpose(`` in the name, or an instruction
XLA rematerialized for the backward pass, ``.remat`` in its name) and
``forward``.  An op under no scope, or whose program the trace does not
describe, is ``unscoped``: on the chip, mostly copies XLA inserted.  A
fusion counts under its own metadata, which is its root's.

Each instant of device time goes to one op (the latest started of those
running), so the layers' times add up to the busy time of
``trace_reader``.
"""
from __future__ import annotations

import bisect
import heapq
import re
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reader as tr

SCOPES = ("engine.sampler", "kgt.grads", "kgt.local_update", "kgt.epilogue",
          "engine.metrics")
LAYERS = ("forward", "backward", "local_update", "epilogue", "sampler",
          "metrics", "unscoped")
_LAYER = {"engine.sampler": "sampler", "kgt.local_update": "local_update",
          "kgt.epilogue": "epilogue", "engine.metrics": "metrics"}
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?=/|$)")
#: The host spans that label idle gaps: the harness's and the engine's.
SPAN_PREFIXES = (tr.SPAN_PREFIX, "engine.")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MODULES_LINE = "XLA Modules"
_PROGRAM = re.compile(r"\((\d+)\)$")

Interval = tr.Interval


def layer_of(instruction: str, op_name: Optional[str]) -> str:
    """The layer of one instruction, from its name and its ``op_name``."""
    scopes = _SCOPE.findall(op_name or "")
    if not scopes:
        return "unscoped"
    scope = scopes[-1]
    if scope != "kgt.grads":
        return _LAYER[scope]
    if "transpose(" in op_name or ".remat" in instruction:
        return "backward"
    return "forward"


# -- protobuf wire format ----------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = b[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(b: bytes, lo: int = 0, hi: Optional[int] = None):
    """``(field number, value)`` of one message in ``b[lo:hi]``; a
    length-delimited value is its ``(start, end)`` in ``b``."""
    i = lo
    hi = len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            step = 8 if wire == 1 else 4
            value, i = b[i:i + step], i + step
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _text(b: bytes, span: Tuple[int, int]) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _varints(b: bytes, value) -> List[int]:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(b, i)
        out.append(v)
    return out


def _instruction(b: bytes, span: Tuple[int, int]):
    """``(id, name, op_name, called computation ids)`` of one
    ``HloInstructionProto`` (name = 1, metadata = 7 → op_name = 2, id = 35,
    called_computation_ids = 38)."""
    iid, name, op_name, called = None, None, None, []
    for f, v in _fields(b, *span):
        if f == 1:
            name = _text(b, v)
        elif f == 7:
            for g, w in _fields(b, *v):
                if g == 2:
                    op_name = _text(b, w) or None
        elif f == 35:
            iid = v
        elif f == 38:
            called.extend(_varints(b, v))
    return iid, name, op_name, called


def _op_names(b: bytes, span: Tuple[int, int]) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every computation of one
    ``HloProto`` (hlo_module = 1 → computations = 3 → instructions = 2,
    id = 5, root_id = 6).  An instruction without an ``op_name`` of its own,
    such as a fusion whose root XLA made without metadata, takes that of the
    computation it calls: its root's, else that of the last instruction
    that has one."""
    comps: Dict[int, tuple] = {}
    for f, module in _fields(b, *span):
        if f != 1:
            continue
        for f, comp in _fields(b, *module):
            if f != 3:
                continue
            cid, root, instrs = None, None, []
            for g, v in _fields(b, *comp):
                if g == 2:
                    instrs.append(_instruction(b, v))
                elif g == 5:
                    cid = v
                elif g == 6:
                    root = v
            comps[cid] = (root, instrs)
    called_names: Dict[int, Optional[str]] = {}

    def own(instr) -> Optional[str]:
        _, _, op_name, called = instr
        return op_name or next(
            (n for n in map(called_name, called) if n), None)

    def called_name(cid: int) -> Optional[str]:
        if cid not in called_names:
            called_names[cid] = None   # a computation calling itself
            root, instrs = comps.get(cid, (None, []))
            names = [(i[0], own(i)) for i in instrs]
            by_id = dict(names)
            called_names[cid] = by_id.get(root) or next(
                (n for _, n in reversed(names) if n), None)
        return called_names[cid]

    return {i[1]: n for _, instrs in comps.values() for i in instrs
            if i[1] is not None and (n := own(i))}


def programs(xspace: bytes) -> Dict[int, Dict[str, str]]:
    """``{program id: {instruction name: op_name}}`` of every program whose
    HLO the trace holds (``XSpace`` planes = 1; ``XPlane`` name = 2,
    event_metadata = 4, stat_metadata = 5; ``XEventMetadata`` id = 1,
    stats = 5; ``XStat`` metadata_id = 1, bytes_value = 6)."""
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, protos, stat_names = None, [], {}
        for f, v in _fields(xspace, *plane):
            if f == 2:
                name = _text(xspace, v)
                if name != METADATA_PLANE:
                    break
            elif f == 4:
                for g, meta in _fields(xspace, *v):
                    if g == 2:
                        protos.extend(_event_protos(xspace, meta))
            elif f == 5:
                for g, entry in _fields(xspace, *v):
                    if g == 2:
                        stat = dict(_fields(xspace, *entry))
                        if 1 in stat and 2 in stat:
                            stat_names[stat[1]] = _text(xspace, stat[2])
        if name == METADATA_PLANE:
            return {pid: _op_names(xspace, span)
                    for pid, stat_id, span in protos
                    if stat_names.get(stat_id) == HLO_PROTO_STAT}
    return {}


def _event_protos(b: bytes, span: Tuple[int, int]):
    """``(event metadata id, stat metadata id, bytes span)`` of each bytes
    stat of one ``XEventMetadata``."""
    pid, stats = None, []
    for f, v in _fields(b, *span):
        if f == 1:
            pid = v
        elif f == 5:
            stat = dict(_fields(b, *v))
            if 6 in stat:
                stats.append((stat.get(1), stat[6]))
    return [(pid, stat_id, value) for stat_id, value in stats]


# -- ops of a trace, by layer -------------------------------------------------

def _stat(event, name: str):
    for key, value in event.stats:
        if key == name:
            return value
    return None


def layered_ops(profile, xspace: bytes, plane=tr.DEVICE_PLANE,
                line_name: Optional[str] = tr.OPS_LINE
                ) -> Dict[int, List[Interval]]:
    """``{chip: (start_ns, end_ns, layer)}`` of the leaf ops of every plane
    whose name ``plane`` matches (its first group is the chip), from the
    line ``line_name`` (every line when None, keeping only events that
    name an HLO instruction, as the CPU's threads do).  An op's program is
    its ``program_id`` stat, or else the enclosing ``XLA Modules`` event
    (named ``module(id)``)."""
    progs = programs(xspace)
    out: Dict[int, List[Interval]] = {}
    for p in profile.planes:
        m = plane.match(p.name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)) if m.groups() else 0, [])
        modules: List[Tuple[float, float, int]] = []
        for line in p.lines:
            if line.name == MODULES_LINE:
                for e in line.events:
                    pm = _PROGRAM.search(e.name)
                    if pm:
                        modules.append((float(e.start_ns), float(e.end_ns),
                                        int(pm.group(1))))
        modules.sort()
        starts = [s for s, _, _ in modules]
        for line in p.lines:
            if line_name is not None and line.name != line_name:
                continue
            for e in line.events:
                hlo_op = _stat(e, "hlo_op")
                if line_name is None and hlo_op is None:
                    continue
                name = (str(hlo_op) if hlo_op is not None
                        else tr.op_name(e.name))
                if tr.CONTAINER.match(name):
                    continue
                s, t = float(e.start_ns), float(e.end_ns)
                pid = _stat(e, "program_id")
                if pid is None and modules:
                    k = bisect.bisect_right(starts, s) - 1
                    if k >= 0 and modules[k][0] <= s <= modules[k][1]:
                        pid = modules[k][2]
                names = progs.get(int(pid), {}) if pid is not None else {}
                ops.append((s, t, layer_of(name, names.get(name))))
    return out


def exclusive_ns(ops: Iterable[Interval], lo: float, hi: float
                 ) -> Dict[str, float]:
    """``{label: ns}`` within ``[lo, hi]``, each instant given to the latest
    started of the ops running then: the labels' times add up to the busy
    time (``trace_reader.busy_ns``)."""
    ops = tr.clip(ops, lo, hi)
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(ops)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(ops)])
    out: Dict[str, float] = {}
    running: List[Tuple[float, float, int]] = []
    done = set()
    t_prev = None
    for t, is_start, i in edges:
        while running and running[0][2] in done:
            heapq.heappop(running)
        if running and t_prev is not None and t > t_prev:
            label = ops[running[0][2]][2]
            out[label] = out.get(label, 0.0) + (t - t_prev)
        t_prev = t
        if is_start:
            s, e, _ = ops[i]
            heapq.heappush(running, (-s, e - s, i))
        else:
            done.add(i)
    return out


def round_ms(layered: Dict[int, List[Interval]], lo: float, hi: float,
             rounds: int) -> Optional[Dict[str, float]]:
    """Device milliseconds per round of each layer in ``[lo, hi]``,
    averaged over the chips that ran an op; None where no op carries a
    scope (a program without them)."""
    chips = [c for c in sorted(layered) if tr.clip(layered[c], lo, hi)]
    if not chips or rounds <= 0:
        return None
    total = {layer: 0.0 for layer in LAYERS}
    for c in chips:
        for layer, ns in exclusive_ns(layered[c], lo, hi).items():
            total[layer] += ns
    if total["unscoped"] == sum(total.values()):
        return None
    return {layer: ns / len(chips) / rounds * 1e-6
            for layer, ns in total.items()}


def host_spans(profile) -> List[Interval]:
    """The harness's and the engine's spans, for labelling idle gaps:
    ``trace_reader.label_gaps`` picks the innermost one open."""
    return sorted(s for prefix in SPAN_PREFIXES
                  for s in tr.host_spans(profile, prefix))
