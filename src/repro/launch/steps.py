"""Step-function builders: the jit-able programs the dry-run lowers and a real
cluster would execute.

* ``build_train_round``  — one full K-GT-Minimax communication round (K local
  DRO-minimax steps + correction + gossip) over the decentralized mesh.
* ``build_prefill_step`` — batched prefill (logits + populated caches) over
  the production/serving mesh.
* ``build_decode_step``  — one-token decode against a seq_len cache.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import AlgorithmConfig, InputShape, MeshConfig, MinimaxConfig, ModelConfig
from repro.core import kgt_minimax as kgt
from repro.core import objectives, topology
from repro.dist import context as dist_ctx
from repro.dist import sharding as sh
from repro.models import model as model_lib


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# ---------------------------------------------------------------------------
# Training round
# ---------------------------------------------------------------------------

def _train_parts(
    model_cfg: ModelConfig,
    shape: InputShape,
    mesh: Mesh,
    mcfg: MeshConfig,
    algo: Optional[AlgorithmConfig] = None,
    minimax: Optional[MinimaxConfig] = None,
    lr_scale=None,
):
    """Shared setup for the per-round and chunked train programs: the
    constrained round_step callable, abstract state/batch/key specs, and
    their shardings."""
    algo = algo or AlgorithmConfig(num_clients=mcfg.num_clients)
    algo = dataclasses.replace(algo, num_clients=mcfg.num_clients)
    if (algo.mixing_impl in ("pallas_packed", "sparse_packed")
            and algo.gossip_backend == "auto"):
        # Under GSPMD the clients dim is mesh-sharded and pallas_call is not
        # SPMD-partitioned over it; the packed-xla oracle keeps the
        # one-collective-per-variable lowering (gather-based for sparse),
        # which is the win at mesh scale.  The Pallas kernels themselves are
        # the single-chip epilogue path.
        algo = dataclasses.replace(algo, gossip_backend="xla")
    minimax = minimax or MinimaxConfig()
    n, k_steps = algo.num_clients, algo.local_steps
    assert shape.global_batch % n == 0, (shape.global_batch, n)
    b_client = shape.global_batch // n

    problem = objectives.dro_problem(
        model_cfg, num_groups=minimax.num_groups, mu=minimax.mu,
        compute_dtype=jnp.bfloat16, remat=mcfg.remat)
    w = topology.mixing_matrix(algo.topology, n)
    # a clients axis over devices splits the state's client dim: keep the
    # dense gossip a contraction, one all-gather a leaf
    clients_sharded = dict(mesh.shape).get(sh.CLIENTS, 1) > 1
    sorted_moe = (model_cfg.moe.dispatch == "sorted"
                  and "moe" in model_cfg.blocks())
    if (clients_sharded and sorted_moe
            and mesh.devices.flat[0].platform == "tpu"):
        # models.moe keeps the grouped products batched over a sharded
        # clients axis, which XLA's TPU ragged-dot refuses
        raise ValueError(
            "sorted MoE dispatch is not supported on a TPU mesh that splits "
            "the clients axis (the TPU's ragged-dot takes no batch "
            "dimension); run it with --mesh host")
    round_fn = kgt.make_round_step(problem, algo, w, lr_scale=lr_scale,
                                   clients_sharded=clients_sharded)

    # ---- abstract state -------------------------------------------------
    x_one = jax.eval_shape(lambda k: model_lib.init_params(model_cfg, k),
                           jax.random.PRNGKey(0))
    rep = lambda t: jax.tree.map(lambda s: _sds((n, *s.shape), s.dtype), t)
    x_sds = rep(x_one)
    y_sds = _sds((n, minimax.num_groups), jnp.float32)
    counters = None
    if problem.counters is not None:
        counters = {c: _sds((), jnp.float32) for c in problem.counter_names}
    state_sds = kgt.KGTState(x=x_sds, y=y_sds, cx=x_sds, cy=y_sds,
                             round=_sds((), jnp.int32), counters=counters)

    # ---- abstract inputs -------------------------------------------------
    tok_shape = (k_steps, n, b_client, shape.seq_len)
    if model_cfg.num_codebooks:
        tok_shape = tok_shape + (model_cfg.num_codebooks,)
    batch_sds: Dict[str, Any] = {
        "tokens": _sds(tok_shape, jnp.int32),
        "labels": _sds(tok_shape, jnp.int32),
        "groups": _sds((k_steps, n, b_client, shape.seq_len), jnp.int32),
    }
    if model_cfg.num_prefix_tokens:
        batch_sds["prefix"] = _sds(
            (k_steps, n, b_client, model_cfg.num_prefix_tokens, model_cfg.d_model),
            jnp.float32)
    key_sds = _sds((k_steps, n, 2), jnp.uint32)

    # ---- shardings -------------------------------------------------------
    x_shard = sh.params_shardings(
        x_sds, mesh, leading_clients=True, param_mode=mcfg.param_mode,
        expert_parallel=mcfg.moe_expert_parallel)
    y_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, P(sh.CLIENTS)), y_sds)
    state_shard = kgt.KGTState(
        x=x_shard, y=y_shard, cx=x_shard, cy=y_shard,
        round=NamedSharding(mesh, P()),
        counters=jax.tree.map(lambda _: NamedSharding(mesh, P()), counters))
    def batch_spec(s):
        parts = [None, sh.CLIENTS, sh.FSDP, sh.MODEL] + [None] * (len(s.shape) - 4)
        return NamedSharding(mesh, P(*parts[: len(s.shape)]))
    batch_shard = jax.tree.map(batch_spec, batch_sds)
    # prefix (K,n,B,P,d): don't shard the P dim over model
    if "prefix" in batch_sds:
        batch_shard["prefix"] = NamedSharding(
            mesh, P(None, sh.CLIENTS, sh.FSDP, None, None))
    key_shard = NamedSharding(mesh, P(None, sh.CLIENTS, None))

    res_axes = sh.residual_axes(mcfg.residual_mode)
    constraint = sh.leading_dims_constraint(mesh, res_axes)
    slots = {}
    if mcfg.attn_heads_sharding:
        # q (B,S,H,D): heads over model (GSPMD: all-to-all from seq-sharded),
        # context back to seq-sharded before out-projection.
        def qkv_fn(x):
            spec = P(sh.FSDP, None, sh.MODEL, None)
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

        def out_fn(x):
            spec = P(sh.FSDP, sh.MODEL, None, None)
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

        slots = {"attn_qkv": qkv_fn, "attn_out": out_fn}
    if clients_sharded:
        slots[dist_ctx.CLIENTS_SHARDED] = lambda x: x

    def round_step(state, batches, keys):
        with dist_ctx.residual_constraint(constraint, **slots):
            return round_fn(state, batches, keys)

    return (round_step, state_sds, batch_sds, key_sds,
            (state_shard, batch_shard, key_shard))


def build_train_round(
    model_cfg: ModelConfig,
    shape: InputShape,
    mesh: Mesh,
    mcfg: MeshConfig,
    algo: Optional[AlgorithmConfig] = None,
    minimax: Optional[MinimaxConfig] = None,
    lr_scale=None,
):
    """Returns (jitted_round_step, state_sds, batch_sds, key_sds, shardings).

    The round state is x=(n, model params), y=(n, G); batches are stacked
    (K, n, B_client, S...).  Residual activations are constrained to
    (fsdp=batch, model=seq) inside each client.
    """
    round_step, state_sds, batch_sds, key_sds, shardings = _train_parts(
        model_cfg, shape, mesh, mcfg, algo=algo, minimax=minimax,
        lr_scale=lr_scale)
    state_shard, batch_shard, key_shard = shardings
    jitted = jax.jit(
        round_step,
        in_shardings=(state_shard, batch_shard, key_shard),
        out_shardings=state_shard,
        donate_argnums=(0,),
    )
    return jitted, state_sds, batch_sds, key_sds, shardings


def build_train_chunk(
    model_cfg: ModelConfig,
    shape: InputShape,
    mesh: Mesh,
    mcfg: MeshConfig,
    *,
    algo: Optional[AlgorithmConfig] = None,
    minimax: Optional[MinimaxConfig] = None,
    lr_scale=None,
    sampler,
    metrics_fn=None,
    log_every: int = 1,
):
    """The scanned multi-round chunk over the decentralized mesh
    (``repro.engine`` execution model under GSPMD).

    Returns ``(build_chunk, state_sds, state_shard)`` where
    ``build_chunk(length)`` is a jitted ``chunk_step(state, final_round)``
    with the sharded state **donated** across chunk calls.  The sampler runs
    inside the scan body; its batches/keys are pinned to the same
    ``(None, clients, fsdp, model)`` layout the per-round program uses, so
    each client's local steps stay confined to its sub-mesh and only gossip
    crosses the clients axis — now once per compiled chunk of R rounds'
    worth of program, not once per dispatch.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import engine as engine_lib

    round_step, state_sds, _, _, shardings = _train_parts(
        model_cfg, shape, mesh, mcfg, algo=algo, minimax=minimax,
        lr_scale=lr_scale)
    state_shard, batch_shard, key_shard = shardings

    def sharded_sampler(round_idx):
        batches, keys = sampler(round_idx)
        batches = jax.tree.map(jax.lax.with_sharding_constraint,
                               batches, batch_shard)
        keys = jax.lax.with_sharding_constraint(keys, key_shard)
        return batches, keys

    def jit_fn(chunk_fn):
        # metrics buffer out_sharding stays unspecified (small, replicated)
        return jax.jit(
            chunk_fn,
            in_shardings=(state_shard, NamedSharding(mesh, P())),
            out_shardings=(state_shard, None),
            donate_argnums=(0,),
        )

    build_chunk = engine_lib.make_chunk_builder(
        round_step, sharded_sampler, metrics_fn, log_every=log_every,
        jit_fn=jit_fn)
    return build_chunk, state_sds, state_shard


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def _serve_batch_axes(mesh: Mesh):
    return (("pod", "data") if "pod" in mesh.axis_names else "data",)


def _axis_size(mesh: Mesh, axis) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(axis, 1)


def _maybe(axis, size: int, mesh: Mesh):
    """axis if size divides by its mesh extent, else None (e.g. batch=1)."""
    return axis if size % _axis_size(mesh, axis) == 0 else None


def _bf16_sds(tree):
    """Serving params are bf16 (inference)."""
    return jax.tree.map(
        lambda s: _sds(s.shape, jnp.bfloat16)
        if jnp.issubdtype(s.dtype, jnp.floating) else s, tree)


def build_prefill_step(model_cfg: ModelConfig, shape: InputShape, mesh: Mesh):
    """prefill(params, batch) -> (logits_last, caches)."""
    params_sds = _bf16_sds(jax.eval_shape(
        lambda k: model_lib.init_params(model_cfg, k), jax.random.PRNGKey(0)))
    b, s = shape.global_batch, shape.seq_len
    tok_shape = (b, s, model_cfg.num_codebooks) if model_cfg.num_codebooks else (b, s)
    batch_sds = {"tokens": _sds(tok_shape, jnp.int32)}
    if model_cfg.num_prefix_tokens:
        batch_sds["prefix"] = _sds(
            (b, model_cfg.num_prefix_tokens, model_cfg.d_model), jnp.float32)
    cache_sds = jax.eval_shape(
        lambda: model_lib.init_cache(model_cfg, b, s, jnp.bfloat16))

    batch_axis = _serve_batch_axes(mesh)[0]
    # serving residual: batch over data, seq over model (sequence parallelism;
    # GSPMD gathers seq around attention and re-scatters — measured strictly
    # better than batch-only TP layout here, see EXPERIMENTS.md §Perf).
    constraint = sh.leading_dims_constraint(mesh, (batch_axis, "model"))

    def prefill(params, batch, caches):
        with dist_ctx.residual_constraint(constraint):
            logits, new_caches, _ = model_lib.forward(
                params, batch, model_cfg, mode="prefill",
                compute_dtype=jnp.bfloat16, caches=caches, last_only=True)
        return logits, new_caches

    p_shard = sh.serve_params_shardings(params_sds, mesh)
    c_shard = _cache_shardings(cache_sds, mesh, batch_axis)
    b_shard = jax.tree.map(
        lambda sds: NamedSharding(
            mesh, P(*([_maybe(batch_axis, sds.shape[0], mesh)]
                      + [None] * (len(sds.shape) - 1)))),
        batch_sds)
    jitted = jax.jit(prefill, in_shardings=(p_shard, b_shard, c_shard),
                     out_shardings=None)
    return jitted, params_sds, batch_sds, cache_sds


def _cache_shardings(cache_sds, mesh: Mesh, batch_axis):
    """(reps, B, …) cache leaves: batch over the data axes; the largest
    trailing dim divisible by the model-axis size over 'model'."""
    n_model = _axis_size(mesh, "model")

    def spec(sds):
        shp = sds.shape
        parts = [None] * len(shp)
        if len(shp) >= 2:
            parts[1] = _maybe(batch_axis, shp[1], mesh)
        cands = [(sz, i) for i, sz in enumerate(shp[2:], start=2)
                 if sz % n_model == 0 and sz >= n_model]
        if cands:
            parts[max(cands)[1]] = "model"
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(spec, cache_sds)


def build_decode_step(model_cfg: ModelConfig, shape: InputShape, mesh: Mesh):
    """decode(params, caches, tokens, pos) -> (logits, new_caches)."""
    params_sds = _bf16_sds(jax.eval_shape(
        lambda k: model_lib.init_params(model_cfg, k), jax.random.PRNGKey(0)))
    b, s = shape.global_batch, shape.seq_len
    tok_shape = (b, 1, model_cfg.num_codebooks) if model_cfg.num_codebooks else (b, 1)
    tok_sds = _sds(tok_shape, jnp.int32)
    cache_sds = jax.eval_shape(
        lambda: model_lib.init_cache(model_cfg, b, s, jnp.bfloat16))
    pos_sds = _sds((), jnp.int32)

    batch_axis = _serve_batch_axes(mesh)[0]
    constraint = sh.leading_dims_constraint(mesh, (batch_axis,))

    def decode(params, caches, tokens, pos):
        with dist_ctx.residual_constraint(constraint):
            return model_lib.decode_step(params, caches, tokens, pos, model_cfg,
                                         compute_dtype=jnp.bfloat16)

    p_shard = sh.serve_params_shardings(params_sds, mesh)
    c_shard = _cache_shardings(cache_sds, mesh, batch_axis)
    t_shard = NamedSharding(
        mesh, P(*([_maybe(batch_axis, tok_shape[0], mesh)]
                  + [None] * (len(tok_shape) - 1))))
    jitted = jax.jit(
        decode,
        in_shardings=(p_shard, c_shard, t_shard, NamedSharding(mesh, P())),
        out_shardings=None,
        donate_argnums=(1,),
    )
    return jitted, params_sds, cache_sds, tok_sds, pos_sds


# ---------------------------------------------------------------------------
# long_500k config variant
# ---------------------------------------------------------------------------

def long_context_variant(model_cfg: ModelConfig) -> ModelConfig:
    """Sub-quadratic variant for long_500k: SSM/hybrid archs are native;
    full-attention archs get a 4096-token sliding window (beyond-paper,
    flagged in the dry-run table)."""
    if model_cfg.arch_type in ("ssm", "hybrid"):
        return model_cfg
    return dataclasses.replace(model_cfg, long_context_window=4096)
