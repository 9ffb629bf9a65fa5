"""End-to-end system tests: the full training driver on reduced models."""
import argparse

import jax.numpy as jnp
import pytest

from repro.launch import train as train_lib


def _args(**over):
    base = dict(
        arch="qwen2-0.5b", reduced=True, algorithm="kgt_minimax", rounds=6,
        clients=2, local_steps=2, batch=2, seq_len=32, groups=4, mu=1.0,
        alpha=0.3, eta_cx=0.02, eta_cy=0.2, eta_s=0.7, topology="ring",
        mixing_impl="dense", gossip_dtype="float32", schedule="constant",
        warmup=0, seed=0, log_every=2, checkpoint_every=0,
        checkpoint_dir="/tmp/repro_test_ckpt", out=None,
    )
    base.update(over)
    return argparse.Namespace(**base)


def test_scan_engine_history_matches_host_engine():
    """--engine scan and --engine host share the sampler and metrics fn;
    the logged histories must agree record for record (the state
    trajectories are bit-identical — tests/test_engine.py)."""
    res_scan = train_lib.train(_args(rounds=4, engine="scan", chunk=3))
    res_host = train_lib.train(_args(rounds=4, engine="host"))
    hs, hh = res_scan["history"], res_host["history"]
    assert [r["round"] for r in hs] == [r["round"] for r in hh] == [0, 2, 3]
    for rs, rh in zip(hs, hh):
        for key in ("f_bar", "mean_loss", "eval_loss", "consensus_x",
                    "y_bar_norm", "corr_x_norm", "corr_y_norm"):
            assert rs[key] == pytest.approx(rh[key], rel=1e-5, abs=1e-7), key


def test_train_rounds_zero_no_history():
    """--rounds 0 / a log grid that never fires must not crash on
    history[-1]."""
    res = train_lib.train(_args(rounds=0))
    assert res["history"] == []
    assert res["final_consensus"] is None


def test_train_driver_end_to_end():
    res = train_lib.train(_args())
    hist = res["history"]
    assert len(hist) >= 2
    assert all(jnp.isfinite(h["f_bar"]) for h in hist)
    assert res["final_consensus"] < 1.0


def test_train_driver_loss_improves():
    res = train_lib.train(_args(rounds=20, eta_cx=0.05, eta_cy=0.2, batch=4))
    hist = res["history"]
    # judged on the held-out loss: it is measured on one fixed batch the
    # optimizer never sees, so it tracks the model.  The train mean_loss is
    # each logged round's own fresh batch, whose draw-to-draw noise is as
    # large as 20 rounds of progress (it rose 3.12 -> 3.67 in a run whose
    # held-out loss fell 1.56 -> 1.39).  The saddle value f(x̄,ȳ) itself is
    # not monotone either (y climbs first).
    assert hist[-1]["eval_loss"] < hist[0]["eval_loss"]


@pytest.mark.parametrize("algorithm", ["dsgda", "local_sgda", "gt_gda"])
def test_train_driver_baselines(algorithm):
    res = train_lib.train(_args(algorithm=algorithm, rounds=4))
    assert all(jnp.isfinite(h["f_bar"]) for h in res["history"])


def test_train_driver_checkpointing(tmp_path):
    train_lib.train(_args(rounds=4, checkpoint_every=2,
                          checkpoint_dir=str(tmp_path)))
    from repro.checkpoint import latest
    assert latest(str(tmp_path)) is not None


def test_scan_engine_honors_checkpoint_cadence(tmp_path):
    """checkpoint_every finer than the chunk must shrink the chunk, not
    silently skip multiples (scan engine saves at chunk boundaries)."""
    import os

    train_lib.train(_args(rounds=6, engine="scan", chunk=16,
                          checkpoint_every=2, checkpoint_dir=str(tmp_path)))
    names = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert names == ["round_000002.npz", "round_000004.npz",
                     "round_000006.npz"]


def test_train_driver_wsd_schedule():
    res = train_lib.train(_args(rounds=6, schedule="wsd", warmup=2,
                                arch="minicpm-2b"))
    assert all(jnp.isfinite(h["f_bar"]) for h in res["history"])


def test_chip_smoke_refuses_the_cpu():
    """chip_smoke.py never falls back to the CPU: with no TPU it exits
    non-zero and prints no result."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=root, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
