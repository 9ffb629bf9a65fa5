"""The benchmark's copy of the synthetic federated token data that the
trainer samples on the device (``repro/data/synthetic.py``,
``repro/engine/sampler.py``), written out so that the plain reference
draws the same tokens from the same seed without importing the program.

G domains, each a unigram distribution over the vocabulary plus a bigram
shift; client i draws each sequence's domain from its Dirichlet(α)
mixture.  Keys follow the trainer's schedule: ``PRNGKey(seed)`` splits into
(data, init, round) keys; round t's batches come from
``fold_in(round key, t)``; the held-out batch from ``fold_in(data key, 2)``
and the correction's initial batch from ``fold_in(data key, 1)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def data_model(key, *, vocab_size, num_groups, num_clients, alpha,
               sharpness=2.0):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    width = min(vocab_size, 4096)
    logits = sharpness * jax.random.normal(k1, (num_groups, width))
    if vocab_size > 4096:
        reps = -(-vocab_size // 4096)
        logits = jnp.tile(logits, (1, reps))[:, :vocab_size]
        logits = logits + 0.01 * jax.random.normal(k3, (num_groups, 1))
    shift = jax.random.randint(k2, (num_groups,), 1, max(2, vocab_size // 7))
    mix = jax.random.dirichlet(k4, jnp.full((num_groups,), alpha),
                               (num_clients,))
    return {"logits": logits, "shift": shift, "mix": mix}


def client_batch(dm, key, client, batch, seq_len):
    vocab = dm["logits"].shape[1]
    kg, kt, kb = jax.random.split(key, 3)
    g = jax.random.categorical(kg, jnp.log(dm["mix"][client] + 1e-9),
                               shape=(batch,))
    first = jax.random.categorical(kt, dm["logits"][g],
                                   shape=(seq_len + 1, batch)).T
    shift = dm["shift"][g][:, None]
    prev = jnp.roll(first, 1, axis=1).at[:, 0].set(first[:, 0])
    use_bigram = jax.random.bernoulli(kb, 0.5, first.shape)
    seq = jnp.where(use_bigram, (prev + shift) % vocab, first)
    groups = jnp.broadcast_to(g[:, None], (batch, seq_len)).astype(jnp.int32)
    return {"tokens": seq[:, :-1], "labels": seq[:, 1:], "groups": groups}


def round_batches(dm, key, *, local_steps, num_clients, batch, seq_len):
    """Batches ``(K, n, B, S)`` for one round."""
    keys = jax.random.split(key, local_steps * num_clients).reshape(
        local_steps, num_clients, 2)

    def one(k, i):
        return client_batch(dm, k, i, batch, seq_len)

    return jax.vmap(lambda ks: jax.vmap(one)(ks, jnp.arange(num_clients)))(
        keys)


def eval_batch(dm, key, *, num_clients, batch, seq_len):
    """The fixed held-out batch, one draw per client, ``(n·B, S)``."""
    rb = round_batches(dm, key, local_steps=1, num_clients=num_clients,
                       batch=batch, seq_len=seq_len)
    return jax.tree.map(
        lambda x: x.reshape((x.shape[1] * x.shape[2],) + x.shape[3:]), rb)
