"""How every Pallas kernel is compiled for the device it runs on.

One decision lives here and nowhere else: a `pallas_call` runs in the
Pallas interpreter exactly when JAX's default backend is not a TPU, and on
a TPU it compiles natively with the VMEM budget of the chip it finds
(`hw.Chip.vmem_budget_bytes`, the same budget the schedule templates
validate against).  No caller passes `interpret`.

A compile-only rehearsal for a described TPU (no chip attached) steers this
module from the test: it replaces `interpret` and `target_chip`.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.experimental.pallas import tpu as pltpu

from repro import hw


def interpret() -> bool:
    """True when Pallas kernels must run interpreted: the backend is not TPU."""
    return jax.default_backend() != "tpu"


def target_chip() -> hw.Chip:
    """The chip the kernels compile for, by the first device's kind."""
    return hw.chip_for_kind(jax.devices()[0].device_kind)


def pallas_call_args(
        dimension_semantics: Optional[Tuple[str, ...]] = None) -> Dict[str, Any]:
    """The `interpret` / `compiler_params` keywords of one `pallas_call`."""
    if interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=target_chip().vmem_budget_bytes)}
