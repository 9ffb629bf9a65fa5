"""End-to-end driver: decentralized DRO training of a real transformer LM
with K-GT-Minimax over heterogeneous clients.

Default is a CPU-sized model (~9M params) for a few hundred rounds; pass
``--full`` on real hardware for the ~100M paper-toy config.

  PYTHONPATH=src python examples/robust_lm.py --rounds 200
"""
import argparse
import dataclasses
import os

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCHS
from repro.launch import train as train_lib

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

SMALL = ModelConfig(
    name="robust-lm-9m", arch_type="dense", num_layers=4, d_model=256,
    num_heads=4, num_kv_heads=2, d_ff=1024, vocab_size=4096,
    tie_embeddings=True, source="this repo (CPU-sized demo)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--full", action="store_true",
                    help="use the ~100M paper-toy config (real hardware)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=0.2,
                    help="Dirichlet heterogeneity (smaller = more heterogeneous)")
    args_in = ap.parse_args()

    if not args_in.full:
        ARCHS["robust-lm-9m"] = SMALL  # register the demo config

    ns = argparse.Namespace(
        arch="paper-toy" if args_in.full else "robust-lm-9m",
        reduced=False, algorithm="kgt_minimax", rounds=args_in.rounds,
        clients=args_in.clients, local_steps=args_in.local_steps, batch=4,
        seq_len=128, groups=8, mu=1.0, alpha=args_in.alpha, eta_cx=0.02,
        eta_cy=0.15, eta_s=0.5, topology="ring", mixing_impl="dense",
        gossip_dtype="float32", schedule="wsd", warmup=10, seed=0,
        log_every=10, checkpoint_every=100, checkpoint_dir="/tmp/robust_lm_ckpt",
        # repro.engine chunked execution: one compiled scan per 10 rounds,
        # checkpoints land on chunk boundaries
        engine="scan", chunk=10, mesh="host",
        out=os.path.join(RESULTS, "robust_lm.json"))
    result = train_lib.train(ns)
    import json

    os.makedirs(RESULTS, exist_ok=True)
    with open(ns.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[robust_lm] wrote {ns.out}")


if __name__ == "__main__":
    main()
