"""The reduction from a profiler trace to the benchmark's per-layer numbers.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU each
chip is a ``/device:TPU:N`` plane.  Its ``XLA Ops`` line holds one event per
executed HLO instruction, named by the instruction's text
(``%fusion.12 = bf16[…] fusion(…)``); the ops of a loop body nest inside
the ``while`` op that runs them.  Its ``Async XLA Ops`` line holds the
in-flight spans of asynchronous copies and collectives.  The harness's own
spans are the ``jax.profiler.TraceAnnotation`` events named ``bench.*`` on
the host threads.  All share the profiler's clock, in nanoseconds.

An op is named by its instruction name (``fusion.12``).  Busy time is the
union of the leaf ops of ``XLA Ops``: control-flow ops (``while``,
``conditional``, ``call``) only enclose other ops and are left out.

Everything below works on plain ``(start_ns, end_ns, name)`` intervals, so
the arithmetic can be checked on any trace, the CPU's included.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
#: Instruction names of ops that move data between chips.
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all)")
#: Instruction names of ops that only enclose other ops.
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
_INSTR = re.compile(r"^%?([\w.\-]+?)(?: = |$)")


def op_name(text: str) -> str:
    """``"%fusion.12 = bf16[…] fusion(…)"`` → ``"fusion.12"``."""
    m = _INSTR.match(text)
    return m.group(1) if m else text


def latest_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def events(line) -> List[Interval]:
    return [(float(e.start_ns), float(e.end_ns), e.name) for e in line.events]


def device_ops(profile, line_name: str = OPS_LINE
               ) -> Dict[int, List[Interval]]:
    """``{chip index: op intervals}`` of one line of every TPU device plane,
    each op named by its instruction name; control-flow ops are dropped
    from the ``XLA Ops`` line."""
    out: Dict[int, List[Interval]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != line_name:
                continue
            for s, e, text in events(line):
                name = op_name(text)
                if line_name == OPS_LINE and CONTAINER.match(name):
                    continue
                ops.append((s, e, name))
    return out


def host_spans(profile, prefix: str = SPAN_PREFIX) -> List[Interval]:
    """The harness's spans: host events whose name starts with ``prefix``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(e for e in events(line) if e[2].startswith(prefix))
    return sorted(out)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    out = []
    for s, e, name in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, name))
    return out


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of ``intervals``."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Time within ``[lo, hi]`` during which at least one interval runs."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle stretches of ``[lo, hi]``: where no interval runs."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    """Length of ``∪a`` not covered by ``∪b`` (both merged and sorted)."""
    total, j = 0.0, 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                total += b[k][0] - t
            t = max(t, b[k][1])
            k += 1
        if e > t:
            total += e - t
    return total


def exposed_collective_ns(ops: Iterable[Interval], lo: float, hi: float,
                          in_flight: Iterable[Interval] = (),
                          is_collective=COLLECTIVE.match) -> float:
    """Time within ``[lo, hi]`` in which a collective runs (as an op, or in
    flight on the asynchronous line) and no other op does: the exchange
    that compute does not hide."""
    ops = clip(ops, lo, hi)
    coll = union([o for o in ops if is_collective(o[2])]
                 + [o for o in clip(in_flight, lo, hi)
                    if is_collective(o[2])])
    comp = union(o for o in ops if not is_collective(o[2]))
    return subtract(coll, comp)


def op_time_ns(ops: Iterable[Interval], pattern: str) -> Tuple[float, int]:
    """Summed duration and count of the ops whose name matches
    ``pattern`` (a regular expression searched in the op name)."""
    rx = re.compile(pattern)
    hits = [e - s for s, e, name in ops if rx.search(name)]
    return float(sum(hits)), len(hits)


def top_ops(ops: Iterable[Interval], k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` op names that took the most device time, in seconds,
    summed over their executions and over chips."""
    tot: Dict[str, float] = {}
    for s, e, name in ops:
        tot[name] = tot.get(name, 0.0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns * 1e-9) for name, ns in best]


def label_gaps(gap_list: Sequence[Tuple[float, float]],
               spans: Sequence[Interval], k: int = 10
               ) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps, each labelled with the innermost harness
    span open at its midpoint (``"none"`` where none is), in seconds."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else "none"
        out.append((name, (e - s) * 1e-9))
    return out


class Trace:
    """One traced window: device ops per chip, the harness's spans and the
    window ``[lo, hi]`` (the ``bench.window`` span)."""

    def __init__(self, ops: Dict[int, List[Interval]],
                 spans: List[Interval], window: Optional[Tuple[float, float]]
                 = None, in_flight: Optional[Dict[int, List[Interval]]] = None):
        if window is None:
            wins = [s for s in spans if s[2] == SPAN_PREFIX + "window"]
            if not wins:
                raise ValueError("the trace holds no bench.window span")
            window = (wins[0][0], wins[0][1])
        self.ops = ops
        self.in_flight = in_flight or {}
        self.spans = spans
        self.lo, self.hi = window

    @classmethod
    def from_dir(cls, directory: str) -> "Trace":
        prof = load(latest_xplane(directory))
        return cls(device_ops(prof), host_spans(prof),
                   in_flight=device_ops(prof, ASYNC_LINE))

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips that ran an operation."""
        chips = [c for c in self.chips() if self.ops[c]]
        if not chips:
            return 0.0
        return sum(busy_ns(self.ops[c], self.lo, self.hi)
                   for c in chips) / len(chips) * 1e-9

    def idle_share(self) -> Optional[float]:
        if not self.chips() or self.hi <= self.lo:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def exposed_collective_s(self) -> float:
        chips = self.chips()
        return sum(exposed_collective_ns(self.ops[c], self.lo, self.hi,
                                         self.in_flight.get(c, ()))
                   for c in chips) / max(len(chips), 1) * 1e-9

    def op_time_s(self, pattern: str) -> Tuple[float, int]:
        """Summed seconds and count of matching ops over all chips."""
        t, n = 0.0, 0
        for c in self.chips():
            dt, dn = op_time_ns(clip(self.ops[c], self.lo, self.hi), pattern)
            t, n = t + dt, n + dn
        return t * 1e-9, n

    def breakdown(self, k: int = 10) -> dict:
        all_ops = [o for c in self.chips()
                   for o in clip(self.ops[c], self.lo, self.hi)]
        chip0 = self.chips()[0] if self.chips() else None
        gap_list = (gaps(self.ops[chip0], self.lo, self.hi)
                    if chip0 is not None else [])
        return {"device_ops": [list(t) for t in top_ops(all_ops, k)],
                "idle_gaps": [list(t) for t in label_gaps(gap_list,
                                                          self.spans, k)]}
