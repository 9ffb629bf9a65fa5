"""Roofline report: derive the three per-device time terms for every
(arch x shape x mesh) entry of the dry-run JSONL.

  compute_s    = parsed dot FLOPs / peak bf16 FLOP/s
  memory_s     = parsed HBM traffic / HBM bandwidth
  collective_s = parsed collective bytes / one ICI link's bandwidth (proxy)

with the peaks of the target chip from :data:`PEAKS`.  FLOPs/traffic/
collective bytes come from the loop-aware HLO parse
(repro.analysis.hlo_cost) — XLA's own cost_analysis counts while bodies once.
MODEL_FLOPS uses 6·N·D (train, N=active params) / 2·N·D (inference) per
device; the ratio against parsed FLOPs measures remat/dispatch overhead.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.launch import mesh as mesh_lib

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
#: of HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links (50 GB/s
#: per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 50e9},
}

#: The chip the dry-run meshes describe.
DRYRUN_DEVICE_KIND = "TPU v5 lite"

DEFAULT_DRYRUN = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "results", "dryrun.jsonl")


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in :data:`PEAKS` raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def model_flops_per_device(arch: str, shape_name: str, mesh_kind: str) -> float:
    cfg = registry.get_model_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    multi = mesh_kind == "multi"
    chips = 512 if multi else 256
    if shape.kind == "train":
        mcfg = mesh_lib.decentralized_mesh_config(arch, multi_pod=multi)
        k_steps = 2  # dry-run AlgorithmConfig default
        tokens_per_client = shape.global_batch // mcfg.num_clients * shape.seq_len
        per_client_chips = mcfg.fsdp * mcfg.model
        return k_steps * 6.0 * n_active * tokens_per_client / per_client_chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / chips
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / chips


def load(path: str) -> List[Dict]:
    return [json.loads(l) for l in open(path) if l.strip()]


def analyze_entry(r: Dict, device_kind: str = DRYRUN_DEVICE_KIND
                  ) -> Optional[Dict]:
    if "error" in r:
        return None
    pk = peaks(device_kind)
    coll = sum(v for k, v in r["collectives"].items() if not k.startswith("n_"))
    compute_s = r["cost"]["dot_flops"] / pk["bf16_flops"]
    memory_s = r["cost"]["traffic_bytes"] / pk["hbm_bytes_per_s"]
    collective_s = coll / pk["ici_link_bytes_per_s"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(r["arch"], r["shape"], r["mesh"])
    useful = mf / r["cost"]["dot_flops"] if r["cost"]["dot_flops"] else 0.0
    return {
        "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "dominant": dominant,
        "model_flops": mf, "useful_ratio": useful,
        "peak_gib": r["memory"]["peak_per_device"] / 2**30,
    }


def what_would_help(row: Dict) -> str:
    d = row["dominant"]
    if d == "collective":
        return ("cut gossip/FSDP bytes: bf16 gossip, ring ppermute, fewer "
                "param regathers per local step")
    if d == "memory":
        return "raise arithmetic intensity: fuse, larger per-chip tiles, remat less"
    if row["useful_ratio"] < 0.4:
        return "compute-bound but wasteful: reduce remat/dispatch FLOPs"
    return "compute-bound near roofline: scale batch or accept"


def table(path: str, meshes=("single",)) -> str:
    rows = [analyze_entry(r) for r in load(path)]
    rows = [r for r in rows if r and r["mesh"] in meshes]
    out = ["| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | 6ND/HLO | peak GiB |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{r['compute_s']:.3f} | {r['memory_s']:.3f} | "
            f"{r['collective_s']:.3f} | **{r['dominant']}** | "
            f"{r['useful_ratio']:.2f} | {r['peak_gib']:.1f} |")
    return "\n".join(out)


def run(csv=print, path: str = DEFAULT_DRYRUN):
    rows = [analyze_entry(r) for r in load(path)]
    rows = [r for r in rows if r]
    for r in rows:
        csv(f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
            f"compute_s={r['compute_s']:.4f},memory_s={r['memory_s']:.4f},"
            f"collective_s={r['collective_s']:.4f},dominant={r['dominant']},"
            f"useful={r['useful_ratio']:.3f}")
    return rows


if __name__ == "__main__":
    import sys

    path = sys.argv[1] if len(sys.argv) > 1 else DEFAULT_DRYRUN
    print(table(path, meshes=("single", "multi")))
