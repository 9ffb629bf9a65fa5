"""The cell ``granite-ep4-n4-k2`` at a size a CPU test run can hold (the
share at its smoke-test width: 2 layers, d=256, 1 of 4 experts held, top-2,
vocabulary 512, seq 32), with the cell's own limits: the fp8 control and
each planted fault fail them, a sound run passes them; the operation counts
of ``moe_flops``; and the pattern by which ``experts_roofline`` finds the
grouped products in a trace."""
import contextlib
import functools
import os
from types import SimpleNamespace

import jax
import pytest

import faults
import run as bench_run
import trace_reader as tr

CELL = "granite-ep4-n4-k2"
SMALL = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 64, "d_ff": 512, "vocab_size": 512, "num_experts": 4,
         "experts_held": 1, "top_k": 2, "expert_d_ff": 64}


def spec():
    s = bench_run.cell_spec(CELL)
    s.config = dict(s.config, **SMALL)
    argv = list(s.traffic["argv"])
    argv[argv.index("--seq-len") + 1] = "32"
    s.traffic = dict(s.traffic, argv=argv + ["--reduced"])
    return s


def entry(s):
    return bench_run.load_module(
        os.path.join(bench_run.BENCH, "entries", s.cell["entry"] + ".py"),
        "bench_entry_" + s.cell["entry"])


def load(name):
    return bench_run.load_module(os.path.join(bench_run.BENCH, name),
                                 "bench_test_" + name.replace("/", "_")
                                 .replace(".", "_"))


@functools.lru_cache(maxsize=None)
def sound():
    return bench_run.execute(spec(), 7, 1.0, False, jax)


def test_a_sound_run_is_correct():
    line, numbers = sound()
    assert line["correct"], numbers
    assert 0.0 <= numbers["routing_flip_share"] < 0.05


def test_control_fails_a_limit():
    s = spec()
    limits = s.cell["limits"]
    for seed in (5, 6):
        numbers = entry(s).control_readings(s, seed)
        over = [k for k, v in numbers.items()
                if k in limits and v > limits[k]]
        assert over, f"seed {seed}: the control passes every limit: {numbers}"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_makes_the_run_incorrect(fault):
    s = spec()
    limits = s.cell["limits"]
    _, ok = sound()
    with faults.FAULTS[fault]():
        line, numbers = bench_run.execute(s, 7, 1.0, False, jax)
    assert not line["correct"], numbers
    caught = [k for k in limits
              if numbers[k] > limits[k] and ok[k] <= limits[k]]
    assert caught or line["failed"], (numbers, ok)


@contextlib.contextmanager
def half_of_each_sequence():
    """The second half of every sequence replaced by the first half: the
    cell's traffic has one sequence per client step, so ``half_batch``
    keeps no row at all there."""
    import jax.numpy as jnp

    from repro import engine

    make = engine.make_dro_sampler

    def make_half(*a, **kw):
        sample = make(*a, **kw)

        def halved(round_idx):
            batches, keys = sample(round_idx)

            def dup(t):
                half = t.shape[3] // 2
                return jnp.concatenate([t[:, :, :, :half]] * 2, axis=3)

            return jax.tree.map(dup, batches), keys

        return halved

    with faults._patched(engine, "make_dro_sampler", make_half):
        yield


def test_half_of_each_sequence_makes_the_run_incorrect():
    s = spec()
    limits = s.cell["limits"]
    _, ok = sound()
    with half_of_each_sequence():
        line, numbers = bench_run.execute(s, 7, 1.0, False, jax)
    assert not line["correct"], numbers
    assert [k for k in limits
            if numbers[k] > limits[k] and ok[k] <= limits[k]], (numbers, ok)


GRANITE_SHARE = {"num_layers": 8, "d_model": 1024, "num_heads": 4,
                 "num_kv_heads": 2, "head_dim": 64, "vocab_size": 12288,
                 "num_experts": 32, "experts_held": 8, "expert_d_ff": 512}


def test_moe_flops_by_hand():
    mf = load("moe_flops.py")
    c = GRANITE_SHARE
    # per layer: q 1024·256, k and v 1024·128 each, o 256·1024, router
    # 1024·32; the tied head 12288·1024
    per_layer = 262_144 + 2 * 131_072 + 262_144 + 32_768
    assert mf.dense_matmul_params(c) == 8 * per_layer + 12_582_912
    assert mf.dense_flops_per_token(c, 1024) == (
        6 * (8 * per_layer + 12_582_912) + 3 * 8 * 4 * 1024 * 4 * 64)
    assert mf.expert_flops_per_row(c) == 6 * 3 * 1024 * 512
    assert mf.train_flops(c, 1024, 10, 7) == (
        10 * mf.dense_flops_per_token(c, 1024) + 7 * 6 * 3 * 1024 * 512)
    work = mf.grouped_products(c, rows=2048, calls=1)
    # 3 products × (forward, input and weight gradients): 2·d·f a row each
    assert work["flops"] == 9 * 2 * 1024 * 512 * 2048
    # each reads its rows' in and out widths in bf16, and 8 experts' weights
    assert work["bytes"] == 9 * (1536 * 2 * 2048 + 8 * 1024 * 512 * 2)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert mf.roofline_s(work, peaks) == work["bytes"] / 819e9


def test_experts_roofline_reads_only_the_grouped_products():
    """Op names as the TPU trace gives them: the grouped products are the
    ragged-dot kernels; the kernels that prepare their groups' metadata,
    the fusions around them and ops outside the window are left out."""
    metric = load("metrics/experts_roofline.py")
    ops = {0: [(0.0, 10.0, "ragged-dot-none.13"),
               (10.0, 12.0, "ragged-dot-metadata.2"),
               (12.0, 20.0, "fusion.ragged-dot.1"),
               (20.0, 35.0, "ragged-dot-none"),
               (35.0, 40.0, "copy.7"),
               (90.0, 99.0, "ragged-dot-none.14")]}
    trace = tr.Trace(ops, [], window=(0.0, 50.0))
    secs, count = trace.op_time_s(metric.PATTERN)
    assert (secs, count) == (pytest.approx(25e-9), 2)
    c = dict(GRANITE_SHARE, expert_d_ff=512)
    run = SimpleNamespace(
        trace=trace, config=c, peaks={"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9},
        counts={"rounds": 2, "moe_rows_held_per_round": 1000.0},
        traffic={"argv": ["--clients", "4", "--local-steps", "2"]})
    mf = load("moe_flops.py")
    work = mf.grouped_products(c, 2000.0, 4 * 2 * 8 * 2)
    assert metric.read(run) == pytest.approx(
        100 * mf.roofline_s(work, run.peaks) / 25e-9)
    run.counts = {"rounds": 2}   # a program without the counter
    assert metric.read(run) is None
