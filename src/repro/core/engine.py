"""Runtime engine (paper §2): "collects the operator functions generated by
automated searches, and drives the data flow expressed by the optimized graph
to complete inference."

The engine turns (Graph, InferencePlan, BackendRegistry) into a single
jit-compiled JAX callable.  Backends:

  'xla'            -> repro.core.ref_ops (the vendor-library lane)
  'pallas_*'       -> repro.kernels.ops tuned kernels (WPK-generated lane);
                      they compile natively on a TPU and run in the
                      Pallas interpreter elsewhere (`kernels.tpu`).

Because the whole executed graph is one jit region, XLA still fuses across
our op boundaries — the engine's value is *which implementation* each compute
op uses, exactly the paper's system-level exploration.  The engine can also
be *embedded*: `as_callable()` returns a pure function usable inside any
larger JAX program (the TensorRT-plugin analogue).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.core import ref_ops
from repro.core.graph import Graph
from repro.core.plan import InferencePlan

# (op_kind, backend) -> fn(inputs: list[Array], attrs, config) -> Array
BackendRegistry = Dict[str, Callable]


def _xla_backend(node_op: str):
    def run(inputs, attrs, config):
        return ref_ops.run_op(node_op, inputs, attrs)
    return run


def default_registry() -> BackendRegistry:
    """Registry with the XLA lane plus every tuned Pallas template."""
    from repro.kernels import ops as kops  # local import: kernels are optional

    return {
        "xla": None,  # resolved per-op via ref_ops
        "pallas_matmul": lambda inputs, attrs, config: kops.matmul(
            *inputs, config=config, activation=attrs.get("activation")),
        "pallas_conv2d": lambda inputs, attrs, config: kops.conv2d(
            *inputs, stride=attrs.get("stride", 1),
            padding=attrs.get("padding", "SAME"),
            layout=attrs.get("layout", "NCHW"),
            activation=attrs.get("activation"),
            config=config),
        "pallas_attention": lambda inputs, attrs, config: kops.attention(
            *inputs, causal=attrs.get("causal", True),
            config=config),
    }


class Engine:
    def __init__(
        self,
        graph: Graph,
        plan: Optional[InferencePlan] = None,
        registry: Optional[BackendRegistry] = None,
        jit: bool = True,
    ):
        graph.validate()
        self.graph = graph
        self.plan = plan
        self.registry = registry
        self._order = graph.toposort()
        self._fn = self.as_callable()
        if jit:
            self._fn = jax.jit(self._fn)

    # ------------------------------------------------------------------
    def as_callable(self) -> Callable[..., List[Any]]:
        graph, plan, registry = self.graph, self.plan, self.registry
        order = self._order
        input_names = list(graph.inputs)
        consts = {k: jax.numpy.asarray(v) for k, v in graph.constants.items()}

        def run(*args):
            assert len(args) == len(input_names), (
                f"expected {len(input_names)} inputs {input_names}")
            env: Dict[str, Any] = dict(consts)
            env.update(zip(input_names, args))
            for node in order:
                ins = [env[t] for t in node.inputs]
                choice = plan.choice(node.name) if plan else None
                if choice is not None and choice.backend != "xla" and registry:
                    out = registry[choice.backend](ins, node.attrs, choice.config)
                else:
                    out = ref_ops.run_op(node.op, ins, node.attrs)
                env[node.outputs[0]] = out
            return [env[o] for o in graph.outputs]

        return run

    def __call__(self, *args):
        return self._fn(*args)

    # ------------------------------------------------------------------
    def verify_against_reference(self, *args, rtol=2e-2, atol=2e-2) -> float:
        """Every optimized plan must be numerically equivalent to the
        unoptimized reference graph.  Returns max abs error."""
        ref_engine = Engine(self.graph, plan=None, registry=None, jit=False)
        ref = ref_engine(*args)
        got = self(*args)
        max_err = 0.0
        for r, g in zip(ref, got):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=rtol, atol=atol)
            max_err = max(max_err, float(np.max(np.abs(np.asarray(g, np.float32)
                                                       - np.asarray(r, np.float32)))))
        return max_err
