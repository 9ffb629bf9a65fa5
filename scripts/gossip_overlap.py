"""Count the state-sized collectives of the compiled training chunk that run
under compute, and those that run exposed.

Runs the four-chip toy cell's job (``bench/traffic/dro-n4-k2-4chip.json``'s
argv, one chunk) through ``repro.launch.train.train()``, keeps the optimized
HLO of the chunk program (``jit_chunk_step``, as ``layout_copies.py`` does),
and finds every collective that moves a state leaf: an all-gather whose
result is a client-stacked f32 leaf of x (the dense gossip's operand), or a
collective-permute of one client's slice of one.  A collective is
``overlapped`` when compute ops (fusions, convolutions, custom calls, loops,
conditionals, calls, copies) are scheduled between its start and its done,
and ``exposed`` otherwise: a synchronous all-gather, or a start whose done
follows it with no compute between.  ``across_loops`` counts those in flight
across a loop (the local steps' layer scans are loops).  The compiler's async collective fusions
(``async-collective-start`` ... ``async-collective-done``, whose steps ride
compute fusions) count from their start to their done.

    python scripts/gossip_overlap.py [--traffic FILE] [--out FILE] [-- EXTRA]

Needs as many devices as the job has clients (four chips, or four CPU
devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, where
collectives are synchronous).  ``EXTRA`` arguments are appended to the job's
argv (``--reduced`` runs the smoke-test widths).  The last line of standard
output is the JSON summary; ``--out`` also writes each collective's detail.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from layout_copies import REPO, chunk_hlo, computations, places  # noqa: E402

_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_NAME = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
#: Ops that do work on the device while a collective is in flight.
COMPUTE = frozenset({"fusion", "convolution", "custom-call", "while",
                     "conditional", "call", "copy", "dot", "sort", "reduce",
                     "scatter", "gather"})
_STARTS = {"all-gather-start": "all-gather-done",
           "collective-permute-start": "collective-permute-done"}


def _balanced(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, "{": 1, ")": -1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return j + 1
    return len(text)


def instruction(line: str):
    """``(name, result type, op, operand names)`` of an HLO instruction
    line, or None."""
    m = _NAME.match(line)
    if not m:
        return None
    i = m.end()
    end = _balanced(line, i) if line[i] == "(" else line.index(" ", i)
    rtype = line[i:end]
    rest = line[end:].lstrip()
    op = rest[:rest.find("(")]
    k = len(line) - len(rest) + len(op)
    operands = re.findall(r"%([\w.\-]+)", line[k:_balanced(line, k)])
    return m.group(1), rtype, op, operands


def _f32_sizes(rtype: str):
    return {math.prod(int(d) for d in dims.split(",") if d)
            for dt, dims in _ARRAY.findall(rtype) if dt == "f32"}


def _is_compute(name: str, op: str) -> bool:
    return op in COMPUTE and not name.startswith("async-collective-")


def state_collectives(hlo: str, shapes, clients: int) -> list:
    """Each collective of the chunk that moves a state leaf: its name, op,
    place (``layout_copies.PLACES``), the compute ops scheduled between its
    start and its done, and whether it is ``overlapped``."""
    full = {math.prod(s) for s in shapes}
    slices = {math.prod(s) // clients for s in shapes}
    comps = computations(hlo)
    where = places(comps)
    gathers_in = {c: [ins for ins in map(instruction, lines)
                      if ins and ins[2] == "all-gather"]
                  for c, lines in comps.items()}
    found = []
    for comp, lines in comps.items():
        if where.get(comp) == "fused":
            continue
        parsed = [(ins, line) for line in lines
                  if (ins := instruction(line))]
        insts = [ins for ins, _ in parsed]
        for k, (name, rtype, op, operands) in enumerate(insts):
            sizes, done = None, None
            if op == "all-gather":
                sizes = _f32_sizes(rtype) & full
            elif op == "collective-permute":
                sizes = _f32_sizes(rtype) & slices
            elif op in _STARTS:
                sizes = _f32_sizes(rtype) & (
                    full if op == "all-gather-start" else slices)
                done = next((j for j in range(k + 1, len(insts))
                             if insts[j][2] == _STARTS[op]
                             and name in insts[j][3]), None)
            elif op == "fusion" and name.startswith("async-collective-start"):
                sizes = _f32_sizes(rtype) & full
                chain = {name}
                for j in range(k + 1, len(insts)):
                    if chain & set(insts[j][3]):
                        chain.add(insts[j][0])
                        if insts[j][0].startswith("async-collective-done"):
                            done = j
                            break
            elif op == "fusion" and not name.startswith("async-collective"):
                # a collective fused into another kernel runs inside it
                for callee in _CALLS.findall(parsed[k][1]):
                    if callee.startswith("async_collective_fusion"):
                        continue
                    for g in gathers_in.get(callee, ()):
                        sizes = (sizes or set()) | (_f32_sizes(g[1]) & full)
            if not sizes:
                continue
            between = ([o for n, _, o, _ in insts[k + 1:done]
                        if _is_compute(n, o)] if done is not None else [])
            found.append({"collective": name, "op": op, "computation": comp,
                          "place": where[comp], "elements": max(sizes),
                          "compute_between": len(between),
                          "loops_between": between.count("while"),
                          "overlapped": bool(between)})
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic",
                    default=os.path.join(REPO, "bench", "traffic",
                                         "dro-n4-k2-4chip.json"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("extra", nargs="*")
    args = ap.parse_args(argv)

    hlo, shapes, job = chunk_hlo(args.traffic, args.seed, args.extra)
    import jax

    clients = int(job[job.index("--clients") + 1])
    found = state_collectives(hlo, shapes, clients)
    summary = {"device": jax.devices()[0].device_kind,
               "devices": jax.device_count(), "argv": job,
               "overlapped": sum(c["overlapped"] for c in found),
               "exposed": sum(not c["overlapped"] for c in found),
               "across_loops": sum(c["loops_between"] > 0 for c in found)}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**summary, "collectives": found}, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
