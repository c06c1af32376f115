"""Backend dispatch: from an `InferencePlan` choice to a kernel callable.

The WPK plan records, per stage-qualified operator, WHICH lane won the race
(`xla` vs a tuned Pallas template) and the tuned schedule config.  This
module is the serve-time bridge that makes those choices executable:

  * a **lane registry** mapping a backend name to a callable with the
    uniform signature ``lane(x, w, *, config, activation)`` —
    `xla` lowers through a plain einsum/`@` (the vendor-library lane) and
    `pallas_matmul` through the tuned `ops.matmul` kernel, with the
    activation fused into the kernel epilogue where the template supports
    it (the XLA lane applies it afterwards, so numerics agree);
  * a **dispatch context** (`matmul_dispatch`) holding a per-stage table
    ``role -> (backend, config)`` for the model's named matmuls
    (``qkv_proj`` / ``mlp_up`` / ``mlp_down`` / ``lm_head``).  The context
    is consulted at *trace* time — the step builders in `repro.launch.steps`
    install it around the jitted program body, so the chosen lane is baked
    into the compiled program and costs nothing per step;
  * `dispatch_dense(role, x, w)` — what `models.common.dense` calls for a
    role-tagged projection.  With no active context (training, the fixed
    batch engine, any non-serve path) it is exactly ``x @ w``.

`PlanRouter.matmul_table(stage)` (see `repro.serve.router`) produces the
tables from a tuned serve plan; unknown roles and planless runs fall back to
the XLA lane, so the runtime stays correct, just untuned.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.kernels.ref import apply_activation

# (backend name, tuned config) — the executable projection of an OpChoice.
MatmulChoice = Tuple[str, Dict[str, Any]]
# role -> choice, one table per serve stage (prefill / decode).
MatmulTable = Dict[str, MatmulChoice]

# The model's routable matmul roles, mirroring the serve graph's
# stage-qualified node names (see repro.serve.router.build_serve_graph).
MATMUL_ROLES = ("qkv_proj", "mlp_up", "mlp_down", "lm_head")

LaneFn = Callable[..., jnp.ndarray]

_LANES: Dict[str, LaneFn] = {}


def register_lane(name: str):
    """Register a matmul lane under `name` (decorator)."""

    def deco(fn: LaneFn) -> LaneFn:
        _LANES[name] = fn
        return fn

    return deco


def lanes() -> Dict[str, LaneFn]:
    """Registered lane name -> callable (copy; mutate via register_lane)."""
    return dict(_LANES)


@register_lane("xla")
def xla_lane(x: jnp.ndarray, w: jnp.ndarray, *, config: Optional[Dict] = None,
             activation: Optional[str] = None) -> jnp.ndarray:
    """Vendor-library lane: plain XLA dot (+ unfused activation)."""
    del config
    return apply_activation(x @ w, activation)


@register_lane("pallas_matmul")
def pallas_matmul_lane(x: jnp.ndarray, w: jnp.ndarray, *,
                       config: Optional[Dict] = None,
                       activation: Optional[str] = None) -> jnp.ndarray:
    """Tuned lane: the Pallas MXU matmul with the searched schedule config;
    the activation (when the role carries one) runs in the kernel epilogue."""
    from repro.kernels import ops  # lazy: keep kernel imports off hot import paths

    return ops.matmul(x, w, config=config, activation=activation)


# ----------------------------------------------------------------- context
_tls = threading.local()


@contextlib.contextmanager
def matmul_dispatch(table: Optional[MatmulTable]):
    """Install a per-stage matmul dispatch table for the enclosed trace.

    Like `sharding.activation_rules`, this is consulted while jit TRACES the
    program, so the table must be installed around the traced body (the
    `repro.launch.steps` builders do this) and its choices become static
    properties of the compiled program."""
    prev = getattr(_tls, "table", None)
    _tls.table = dict(table or {})
    try:
        yield
    finally:
        _tls.table = prev


def active_table() -> Optional[MatmulTable]:
    """The currently installed table (None outside a dispatch context)."""
    return getattr(_tls, "table", None)


def dispatch_dense(role: Optional[str], x: jnp.ndarray, w: jnp.ndarray,
                   activation: Optional[str] = None) -> jnp.ndarray:
    """Route one role-tagged projection through the chosen lane.

    Outside a dispatch context — or for a role the table does not name —
    this is the XLA lane, i.e. exactly `x @ w` (+ activation)."""
    table = getattr(_tls, "table", None)
    if table is None or role is None:
        return xla_lane(x, w, activation=activation)
    backend, config = table.get(role, ("xla", {}))
    lane = _LANES.get(backend)
    if lane is None:
        raise KeyError(
            f"plan chose unknown matmul backend {backend!r} for role "
            f"{role!r}; registered lanes: {sorted(_LANES)}")
    return lane(x, w, config=config, activation=activation)
