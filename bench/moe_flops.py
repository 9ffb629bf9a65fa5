"""Operations and bytes of a sparse-expert decoder's training step, from
its shapes and from the rows actually routed to the experts held here
(``moe_rows_held``, the program's counter): the work the algorithm needs,
never what a compiler emitted.

* :func:`dense_flops_per_token` — everything but the experts, per training
  token: ``flops.py``'s dense decoder without its MLP (attention
  projections, the LM head, the scores over all ``seq_len`` keys), plus the
  router over all experts at 6 FLOPs per weight.
* :func:`expert_flops_per_row` — the three SwiGLU products of one
  (token, choice) row routed to a held expert, forward and backward.
* :func:`grouped_products` — FLOPs and HBM bytes of the grouped products
  (forward, and the backward's input and weight gradients) for a number of
  rows over a number of (client, layer, local step) calls of each product.
"""
from __future__ import annotations

import flops

BF16 = 2


def dense_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product once per token, the
    experts left out: ``flops.py``'s dense count with no MLP, plus the
    router over all experts."""
    router = cfg["num_layers"] * cfg["d_model"] * cfg["num_experts"]
    return flops.dense_lm_matmul_params(dict(cfg, d_ff=0)) + router


def dense_flops_per_token(cfg: dict, seq_len: int) -> float:
    """``flops.py``'s dense count per training token with no MLP, plus the
    router at 6 FLOPs per weight."""
    router = cfg["num_layers"] * cfg["d_model"] * cfg["num_experts"]
    return (flops.dense_lm_train_flops_per_token(dict(cfg, d_ff=0), seq_len)
            + 6.0 * router)


def expert_flops_per_row(cfg: dict) -> float:
    """Gate, up and down (3·d·f weights) at 6 FLOPs per weight."""
    return 6.0 * 3 * cfg["d_model"] * cfg["expert_d_ff"]


def train_flops(cfg: dict, seq_len: int, tokens: float, rows: float) -> float:
    """Model FLOPs of ``tokens`` trained tokens whose layers routed
    ``rows`` (token, choice) rows to held experts in all."""
    return (dense_flops_per_token(cfg, seq_len) * tokens
            + expert_flops_per_row(cfg) * rows)


def grouped_products(cfg: dict, rows: float, calls: int) -> dict:
    """FLOPs and bytes of the grouped products for ``rows`` routed rows
    spread over ``calls`` (client, layer, local step) triples.  Each of the
    three products runs three times a call: forward (rows × in → out), the
    input gradient (rows × out → in) and the weight gradient (rows × in and
    rows × out → held experts' weights); each reads its rows' operands and
    writes its result in bf16, and reads or writes the held experts'
    weights for the product once."""
    d, f, held = cfg["d_model"], cfg["expert_d_ff"], cfg["experts_held"]
    flops = 3 * 3 * 2.0 * d * f * rows
    row_bytes = 3 * 3 * (d + f) * BF16 * rows
    weight_bytes = 3 * 3 * held * d * f * BF16 * calls
    return {"flops": flops, "bytes": row_bytes + weight_bytes}


def roofline_s(work: dict, peaks: dict) -> float:
    """The least time the chip could take: FLOPs over peak or bytes over
    HBM bandwidth, whichever is larger."""
    return max(work["flops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
