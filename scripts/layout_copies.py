"""Count the layout-changing copies of the f32 round state in the compiled
training chunk.

Runs the toy cell's job (``bench/traffic/dro-n4-k2.json``'s argv, one chunk)
through ``repro.launch.train.train()``, keeps the optimized HLO of the
chunk program (``jit_chunk_step``), and lists every ``copy`` instruction
whose operand and result are f32 arrays of a state leaf's shape (x, cx and
what the round loop carries of them) with different layouts, counted by
where it runs (``PLACES``): ``per_call`` once per chunk, ``per_iteration``
in every round (or local step), ``in_branch`` under a conditional inside
the loop (the metrics of logged rounds), ``fused`` inside another kernel.

    python scripts/layout_copies.py [--traffic FILE] [--out FILE] [-- EXTRA]

``EXTRA`` arguments are appended to the job's argv (``--reduced`` runs the
smoke-test widths on the CPU).  The last line of standard output is the
JSON summary; ``--out`` also writes it with each copy's detail.  Layouts
differ by backend: the counts that matter are those compiled for the TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_LOOP = re.compile(r"(?:body|condition)=%([\w.\-]+)")
_CALL = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_BRANCH = re.compile(r"(?:true_computation|false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_ARRAY = r"(\w+)\[([\d,]*)\]\{([^}]*)\}"
_DEF = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = " + _ARRAY)
_COPY = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = " + _ARRAY
                   + r" copy\((?:" + _ARRAY + r" )?%([\w.\-]+)\)")
_MEMSPACE = re.compile(r"S\(\d+\)")

#: Where a copy runs: once per call of the program, in every iteration of a
#: loop, under a conditional inside a loop (e.g. only on logged rounds), or
#: fused into another instruction's kernel (no op of its own).
PLACES = ("per_call", "per_iteration", "in_branch", "fused")


def computations(hlo: str) -> dict:
    """Computation name → its instruction lines."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    return comps


def places(comps: dict) -> dict:
    """Computation name → its place (``PLACES``); the entry and what it
    calls outside any loop are ``per_call``."""
    fused = {c for lines in comps.values() for line in lines
             if " fusion(" in line for c in _CALL.findall(line)}
    rank = {"per_call": 0, "in_branch": 1, "per_iteration": 2}
    place = {}
    todo = [(c, "per_call") for c in comps]
    while todo:
        comp, where = todo.pop()
        if comp not in comps or rank[where] <= rank.get(place.get(comp), -1):
            continue
        place[comp] = where
        for line in comps[comp]:
            for c in _LOOP.findall(line):
                todo.append((c, "per_iteration"))
            for c in _CALL.findall(line):
                todo.append((c, where))
            branches = _BRANCH.findall(line) + [
                c.strip().lstrip("%") for g in _BRANCHES.findall(line)
                for c in g.split(",")]
            for c in branches:
                todo.append((c, "in_branch" if where != "per_call"
                             else "per_call"))
    return {c: ("fused" if c in fused else p) for c, p in place.items()}


def state_copies(hlo: str, shapes) -> list:
    """The layout-changing f32 copies of a value of one of ``shapes``."""
    comps = computations(hlo)
    where = places(comps)
    found = []
    for comp, lines in comps.items():
        # the compiled text names a copy's operand without its type: take
        # it from the operand's own definition in the computation
        types = {m.group(1): m.groups()[1:] for m in map(_DEF.match, lines)
                 if m}
        for line in lines:
            m = _COPY.match(line)
            if not m:
                continue
            name, rdt, rdims, rlay = m.groups()[:4]
            operand = m.group(8)
            odt, odims, olay = (m.groups()[4:7] if m.group(5)
                                else types.get(operand, (None,) * 3))
            dims = tuple(int(d) for d in rdims.split(",") if d)
            # S(n) names a memory space: a move between spaces keeps layout
            if (rdt == odt == "f32" and rdims == odims and dims in shapes
                    and _MEMSPACE.sub("", rlay) != _MEMSPACE.sub("", olay)):
                found.append({"copy": name, "operand": operand,
                              "shape": list(dims), "from": olay, "to": rlay,
                              "computation": comp, "place": where[comp]})
    return found


def chunk_hlo(traffic: str, seed: int = 0, extra=()):
    """Run one chunk of the job in ``traffic`` (a ``bench/traffic`` file)
    through ``train()``; return the optimized HLO of its chunk program, the
    shapes of the f32 state leaves (x and cx) and the argv run."""
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    from repro.launch import train as train_lib

    with open(traffic) as f:
        job = json.load(f)["argv"]
    chunk = int(job[job.index("--chunk") + 1])
    job = job + ["--rounds", str(chunk), "--seed", str(seed), *extra]

    texts = []
    original = jax.stages.Lowered.compile

    def compile_and_keep(lowered, *a, **kw):
        compiled = original(lowered, *a, **kw)
        texts.append(compiled.as_text())
        return compiled

    shapes = set()

    def keep_shapes(state, records, prev_round):
        for leaf in jax.tree.leaves((state.x, state.cx)):
            if leaf.dtype == jax.numpy.float32:
                shapes.add(tuple(leaf.shape))

    jax.stages.Lowered.compile = compile_and_keep
    try:
        train_lib.train(train_lib.build_parser().parse_args(job),
                        hooks=[keep_shapes])
    finally:
        jax.stages.Lowered.compile = original
    chunks = [t for t in texts if t.startswith("HloModule jit_chunk_step")]
    if len(chunks) != 1:
        raise RuntimeError(f"expected one compiled chunk, got {len(chunks)}")
    return chunks[0], shapes, job


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic",
                    default=os.path.join(REPO, "bench", "traffic",
                                         "dro-n4-k2.json"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("extra", nargs="*")
    args = ap.parse_args(argv)

    hlo, shapes, job = chunk_hlo(args.traffic, args.seed, args.extra)
    import jax

    found = state_copies(hlo, shapes)
    summary = {"device": jax.devices()[0].device_kind, "argv": job,
               **{p: sum(c["place"] == p for c in found) for p in PLACES}}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**summary, "copies": found}, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
