"""Thread-local distribution context: tagged activation-constraint switches.

The model stack (``repro.models``) is written once, mesh-agnostic.  Layout
decisions — where the residual stream lives, whether attention shards heads
over ``model`` — belong to the step builders in ``repro.launch.steps``,
which know the mesh and the ``MeshConfig``.  This module is the conduit: a
builder wraps tracing in :func:`residual_constraint`, registering constraint
functions under string tags; the model calls :func:`apply` at the tagged
program points (``transformer.block_forward``: ``"attn_qkv"`` after the QKV
projection, ``"attn_out"`` before the out-projection) and
:func:`apply_residual` after each scanned unit.  With no context installed
every call is the identity, so plain CPU tests and the single-device
serving demo run the exact same model code with zero sharding machinery.

The stack is *thread-local* because jit tracing happens on the calling
thread: two threads AOT-compiling different meshes (e.g. the dry-run
driving train and serve builds) cannot observe each other's slots.  Frames
nest innermost-wins per tag, falling through to outer frames for tags the
inner one doesn't define.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, Optional

ConstraintFn = Callable[[Any], Any]

# Slot name used for the residual-stream constraint (``apply_residual``).
RESIDUAL = "residual"
# Slot whose presence says that the clients axis is split over devices:
# model code under the round's vmap then leaves its ops batched
# (``models.moe``'s grouped products; see :func:`has`).
CLIENTS_SHARDED = "clients_sharded"

_tls = threading.local()


def _stack():
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current_slots() -> Dict[str, ConstraintFn]:
    """Effective tag -> constraint mapping (outer frames shadowed by inner).

    Diagnostic / test helper; the hot path is :func:`apply`.
    """
    out: Dict[str, ConstraintFn] = {}
    for frame in _stack():
        out.update(frame)
    return out


def apply(tag: str, x):
    """Apply the innermost constraint registered under ``tag``, or identity.

    Called from traced model code, so the lookup must be cheap and must not
    capture tracers: the constraint fns themselves close over the mesh and
    ``PartitionSpec`` only (see
    ``repro.dist.sharding.leading_dims_constraint``).
    """
    for frame in reversed(_stack()):
        fn = frame.get(tag)
        if fn is not None:
            return fn(x)
    return x


def has(tag: str) -> bool:
    """Whether any installed frame registers ``tag``."""
    return any(tag in frame for frame in _stack())


def apply_residual(x):
    """Re-pin the residual stream to the installed layout (identity if none).

    The model stack calls this once per scanned unit so the residual's
    sharding — ``(fsdp, model)`` or ``(fsdp,)`` per
    ``MeshConfig.residual_mode``, see ``repro.dist.sharding.residual_axes``
    — stays fixed across ``lax.scan`` iterations instead of drifting with
    GSPMD propagation.
    """
    return apply(RESIDUAL, x)


@contextlib.contextmanager
def residual_constraint(residual: Optional[ConstraintFn] = None,
                        **slots: ConstraintFn):
    """Install constraint functions for the dynamic extent of a trace.

    ``residual`` becomes the :func:`apply_residual` target; keyword slots
    register additional tagged switches (``attn_qkv`` / ``attn_out`` for the
    Megatron-SP-style ``attn_heads_sharding`` option).  Usage, from
    ``repro.launch.steps.build_train_round``::

        with dist_ctx.residual_constraint(constraint, **head_slots):
            return round_fn(state, batches, keys)   # traced under jit

    Re-entrant: nested ``with`` blocks shadow outer tags and restore them on
    exit, so a serving builder can temporarily override only the residual
    while keeping an ambient head-sharding slot.
    """
    frame = dict(slots)
    if residual is not None:
        frame[RESIDUAL] = residual
    stack = _stack()
    stack.append(frame)
    try:
        yield
    finally:
        stack.pop()
