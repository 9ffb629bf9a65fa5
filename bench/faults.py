"""Faults planted underneath the timed path, to show that the comparison
catches them.  Each is a context manager that patches the program while it
is active; they are for the fault tests and for reading a fault's numbers
on the chip (``readings.py``), never for the benchmark's own runs.

* ``frozen_state`` — a step that returns its state unchanged (only the
  round counter moves);
* ``half_batch`` — the second half of every client's batch replaced by the
  first half, so the gradient is the mean over half the batch;
* ``no_exchange`` — gossip left out: every client keeps its own values.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


@contextlib.contextmanager
def frozen_state():
    import dataclasses

    from repro.core import kgt_minimax as kgt

    make = kgt.make_round_step

    def make_frozen(*a, **kw):
        step = make(*a, **kw)

        def frozen(state, *args):
            new = step(state, *args)
            return dataclasses.replace(state, round=new.round)

        return frozen

    with _patched(kgt, "make_round_step", make_frozen):
        yield


@contextlib.contextmanager
def half_batch():
    import jax
    import jax.numpy as jnp

    from repro import engine

    make = engine.make_dro_sampler

    def make_half(*a, **kw):
        sample = make(*a, **kw)

        def halved(round_idx):
            batches, keys = sample(round_idx)

            def dup(t):
                half = t.shape[2] // 2
                return jnp.concatenate([t[:, :, :half]] * 2, axis=2)

            return jax.tree.map(dup, batches), keys

        return halved

    with _patched(engine, "make_dro_sampler", make_half):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.core import mixing

    with _patched(mixing, "mix_dense", lambda tree, w, gossip_dtype=None:
                  tree):
        yield


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "no_exchange": no_exchange}
