import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init).  512 placeholder host devices back both the
# single-pod (256-chip) and 2-pod (512-chip) production meshes.

"""Multi-pod dry-run: AOT-lower + compile every (architecture x input shape)
on the production mesh(es), and extract the roofline raw terms.

  train_4k            -> one full K-GT-Minimax round on the decentralized mesh
  prefill_32k         -> batched prefill on the serving mesh
  decode_32k/long_500k-> one-token decode against a seq_len cache

Per entry we record memory_analysis (proves it fits), cost_analysis (FLOPs /
bytes for the roofline), and per-collective byte totals parsed from the
compiled HLO.  Results append to a JSONL (skip-if-done), so the full 40x2
matrix can be built up incrementally.

Usage:
  python -m repro.launch.dryrun --archs qwen2-0.5b --shapes train_4k --meshes single
  python -m repro.launch.dryrun --all
"""
import argparse
import json
import re
import time
import traceback
from typing import Dict

import jax
import jax.numpy as jnp

from repro.analysis import hlo_cost
from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.dist import compat
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib


def _fmt_bytes(b):
    return f"{b/2**30:.2f}GiB"


# Named perf variants (§Perf hillclimb).  "baseline" is the paper-faithful
# configuration: dense-W fp32 gossip, two exchanges/variable, FSDP-2D params.
import dataclasses as _dc

from repro.configs.base import AlgorithmConfig as _Algo

VARIANTS = {
    "baseline": dict(),
    # label-only variants: same config, used to snapshot code-level changes
    "grouped_gqa": dict(),
    "bf16_gossip": dict(algo=dict(gossip_dtype="bfloat16")),
    "replicated": dict(mesh=dict(param_mode="replicated")),
    "expert_parallel": dict(mesh=dict(moe_expert_parallel=True)),
    "no_remat": dict(mesh=dict(remat=False)),
    "attn_heads": dict(mesh=dict(attn_heads_sharding=True)),
    "batch_residual": dict(mesh=dict(residual_mode="batch")),
    "best": dict(mesh=dict(moe_expert_parallel=True)),
    "moe_sorted": dict(moe=dict(dispatch="sorted")),
    "moe_sorted_ep": dict(moe=dict(dispatch="sorted"),
                          mesh=dict(moe_expert_parallel=True)),
}


def run_pair(arch_id: str, shape_name: str, mesh_kind: str,
             variant: str = "baseline") -> Dict:
    multi = mesh_kind == "multi"
    shape = SHAPES[shape_name]
    cfg = registry.get_model_config(arch_id)
    rec = dict(arch=arch_id, shape=shape_name, mesh=mesh_kind, variant=variant)
    over = VARIANTS[variant]
    if over.get("moe") and cfg.moe.num_experts:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, **over["moe"]))
    # monotonic clock for the compile interval (NTP slew can make wall-clock
    # deltas negative); REPRO_COMPILE_CACHE arms jax's
    # persistent compilation cache so repeated dry-runs skip the backend
    # compile (the AOT layer doesn't apply: dry-runs never execute)
    from repro.sweep import cache as cache_lib

    cache_lib.from_env()
    t0 = time.perf_counter()

    if shape.kind == "train":
        mcfg = mesh_lib.decentralized_mesh_config(arch_id, multi_pod=multi)
        if over.get("mesh"):
            mcfg = _dc.replace(mcfg, **over["mesh"])
        algo = _Algo(num_clients=mcfg.num_clients, **over.get("algo", {}))
        mesh = mesh_lib.make_decentralized_mesh(mcfg)
        rec["mesh_shape"] = dict(zip(mesh.axis_names, mesh.devices.shape))
        with compat.use_mesh(mesh):
            jitted, state_sds, batch_sds, key_sds, _ = steps_lib.build_train_round(
                cfg, shape, mesh, mcfg, algo=algo)
            lowered = jitted.lower(state_sds, batch_sds, key_sds)
            compiled = lowered.compile()
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi)
        rec["mesh_shape"] = dict(zip(mesh.axis_names, mesh.devices.shape))
        mcfg_model = cfg
        if shape.name == "long_500k":
            mcfg_model = steps_lib.long_context_variant(cfg)
            rec["variant"] = (
                "native-subquadratic" if mcfg_model is cfg else "sliding-window-4096")
        with compat.use_mesh(mesh):
            if shape.kind == "prefill":
                jitted, p_sds, b_sds, c_sds = steps_lib.build_prefill_step(
                    mcfg_model, shape, mesh)
                lowered = jitted.lower(p_sds, b_sds, c_sds)
            else:
                jitted, p_sds, c_sds, t_sds, pos_sds = steps_lib.build_decode_step(
                    mcfg_model, shape, mesh)
                lowered = jitted.lower(p_sds, c_sds, t_sds, pos_sds)
            compiled = lowered.compile()

    rec["compile_s"] = round(time.perf_counter() - t0, 3)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
    }
    rec["memory"]["peak_per_device"] = (
        rec["memory"]["argument_bytes"] + rec["memory"]["output_bytes"]
        + rec["memory"]["temp_bytes"] - rec["memory"]["alias_bytes"])

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # jax 0.4.x: one dict per program
        cost = cost[0] if cost else {}
    rec["cost_xla"] = {  # XLA's own numbers (counts while bodies once)
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    # loop-aware parsed costs (per device) — the roofline source of truth
    summary = hlo_cost.analyze(compiled.as_text())
    rec["cost"] = {
        "dot_flops": summary.dot_flops,
        "traffic_bytes": summary.traffic_bytes,
        "transcendental_elems": summary.transcendental_elems,
    }
    rec["collectives"] = {
        **{k: float(v) for k, v in summary.collective_bytes.items()},
        **{f"n_{k}": float(v) for k, v in summary.collective_counts.items()},
    }
    print(
        f"[dryrun] {arch_id} x {shape_name} x {mesh_kind} [{variant}]: "
        f"compile {rec['compile_s']}s  "
        f"peak/device {_fmt_bytes(rec['memory']['peak_per_device'])}  "
        f"TFLOPs/dev {summary.dot_flops/1e12:.2f}  "
        f"coll {summary.total_collective_bytes()/2**30:.3f}GiB",
        flush=True,
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="*", default=None)
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--meshes", nargs="*", default=["single", "multi"])
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="/root/repo/results/dryrun.jsonl")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = args.archs or list(registry.ASSIGNED)
    shapes = args.shapes or list(SHAPES)
    meshes = args.meshes

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    done = set()
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], r["mesh"],
                                  r.get("variant", "baseline")))
                except json.JSONDecodeError:
                    pass

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                if (arch, shape, mesh_kind, args.variant) in done:
                    print(f"[dryrun] skip done {arch} x {shape} x {mesh_kind}")
                    continue
                try:
                    rec = run_pair(arch, shape, mesh_kind, args.variant)
                except Exception as e:  # record and continue
                    rec = dict(arch=arch, shape=shape, mesh=mesh_kind,
                               variant=args.variant,
                               error=f"{type(e).__name__}: {e}",
                               trace=traceback.format_exc()[-2000:])
                    print(f"[dryrun] FAIL {arch} x {shape} x {mesh_kind}: {rec['error']}",
                          flush=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
