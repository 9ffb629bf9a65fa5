"""MinimaxProblem: the NC-SC problem abstraction Algorithm 1 optimizes.

A problem supplies per-client value/gradient oracles written for a *single*
client; the algorithm layer vmaps them over the leading clients dim.  The
stochastic oracle receives a per-(round, local-step, client) PRNG key and a
per-client data batch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax


@dataclasses.dataclass(frozen=True)
class MinimaxProblem:
    """NC-SC minimax problem  min_x max_y (1/n) Σ_i f_i(x, y)."""

    # init_x(key) -> x pytree ; init_y(key) -> y pytree (shared across clients)
    init_x: Callable[[Any], Any]
    init_y: Callable[[Any], Any]
    # value(x, y, batch, key) -> scalar f_i(x, y; xi).  The client identity
    # enters through ``batch`` (its data shard) — f_i = f(.; D_i).
    value: Callable[[Any, Any, Any, Any], Any]
    # Optional exact diagnostics (available for the synthetic quadratic):
    # phi_grad(x) -> dPhi/dx of the *global* primal function.
    phi_grad: Optional[Callable[[Any], Any]] = None
    # Optional deterministic full-batch gradient oracle (diagnostics).
    full_grads: Optional[Callable[[Any, Any], Any]] = None
    # Optional affine-gradient coefficient oracle for problems whose per-client
    # stochastic gradient is affine in the packed z = (x; y):
    #   affine_coeffs(batch, key) -> (G, h)  with  (∇x f, ∇y f) = split(G z + h)
    # for a single client (same batch/key semantics as ``grads``, including the
    # noise key split).  The fused-round kernel (kernels/fused_round.py) needs
    # this to run all K local steps in-register; ``None`` means the problem has
    # no affine form and mixing_impl="fused_round" must be rejected.
    affine_coeffs: Optional[Callable[[Any, Any], Any]] = None
    mu: float = 1.0
    # The oracle with its statistics: value_with_stats(x, y, batch, key) ->
    # (value, stats); None means (value(...), None).  counters(stats) ->
    # {name: f32 scalar} for the stats of one round, stacked over (local
    # step, client); the round keeps them in ``KGTState.counters`` under
    # ``counter_names``.  A problem without counters leaves both unset.
    value_with_stats: Optional[Callable[[Any, Any, Any, Any], Any]] = None
    counters: Optional[Callable[[Any], Any]] = None
    counter_names: tuple = ()

    def grads_with_stats(self, x, y, batch, key):
        """``((∇x f_i, ∇y f_i), stats)`` at (x, y) on ``batch`` with noise
        key ``key``; ``stats`` is None without ``value_with_stats``."""
        f = self.value_with_stats or (lambda *a: (self.value(*a), None))
        return jax.grad(f, argnums=(0, 1), has_aux=True)(x, y, batch, key)

    def grads(self, x, y, batch, key):
        """(∇x f_i, ∇y f_i) at (x, y) on ``batch`` with noise key ``key``."""
        return self.grads_with_stats(x, y, batch, key)[0]

    def phi_grad_norm(self, x) -> Any:
        assert self.phi_grad is not None, "problem lacks exact Phi oracle"
        g = self.phi_grad(x)
        import jax.numpy as jnp

        return jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(g)))
