"""System-level exploration (paper §2.5).

For every compute operator in an optimized graph, race the available
implementations — the "vendor library" (XLA lowering, the cuDNN analogue)
and every applicable tuned Pallas template (the WPK-generated-code analogue)
— and single out the fastest for the inference plan.  The paper stresses
this is what distinguishes WPK from XLA/TVM/nGraph: it is not married to its
own codegen.

`select` also honours `third_party=False` to reproduce the paper's §3.4
ablation ("excluding these TensorRT operators incorporated only results in
very marginal performance loss of 2%") — here 'third-party' means the
non-WPK backend (XLA).
"""

from __future__ import annotations

from typing import Dict, Optional

from repro import hw
from repro.core import costmodel
from repro.core.graph import Graph, Node
from repro.core.plan import InferencePlan, OpChoice
from repro.core.schedules import OpDesc, templates_for
from repro.core.search.tuner import Tuner

TUNABLE_OPS = ("conv2d", "fused_conv2d", "matmul", "fused_matmul", "attention")


def op_desc_of(graph: Graph, node: Node, dtype: str = "bfloat16") -> Optional[OpDesc]:
    """Lift a graph node into the hardware-relevant OpDesc."""
    if node.op in ("conv2d", "fused_conv2d"):
        x = graph.tensors[node.inputs[0]].shape
        w = graph.tensors[node.inputs[1]].shape
        layout = node.attrs.get("layout", "NCHW")
        if layout == "NCHW":
            n, cin, h, wd = x
            cout, _, kh, kw = w
        else:
            n, h, wd, cin = x
            kh, kw, _, cout = w
        return OpDesc.conv2d(n, h, wd, cin, cout, kh, kw,
                             stride=node.attrs.get("stride", 1),
                             padding=node.attrs.get("padding", "SAME"),
                             dtype=dtype, activation=node.attrs.get("activation"),
                             label=node.name)
    if node.op in ("matmul", "fused_matmul"):
        x = graph.tensors[node.inputs[0]].shape
        w = graph.tensors[node.inputs[1]].shape
        m = 1
        for s in x[:-1]:
            m *= s
        return OpDesc.matmul(m, w[-1], x[-1], dtype=dtype,
                             activation=node.attrs.get("activation"), label=node.name)
    if node.op == "attention":
        q = graph.tensors[node.inputs[0]].shape
        k = graph.tensors[node.inputs[1]].shape
        b, qlen, heads, hd = q
        return OpDesc.attention(b, qlen, k[1], heads, hd, dtype=dtype,
                                label=node.name, kv_heads=k[2])
    return None


def _race_backends(op: OpDesc, tuner: Tuner, chip: hw.Chip,
                   third_party: bool):
    """Race the vendor (XLA) lane against every applicable tuned Pallas
    template for ONE op shape; -> (backend, cfg, time, candidates)."""
    candidates: Dict[str, float] = {}
    best_backend, best_cfg, best_t = None, {}, float("inf")
    if third_party:  # the vendor/third-party lane of the race
        t_xla = costmodel.xla_time(op, chip)
        candidates["xla"] = t_xla
        best_backend, best_cfg, best_t = "xla", {}, t_xla
    for template in templates_for(op):
        res = tuner.tune(op, template)
        candidates[template.name] = res.runtime_s
        if res.runtime_s < best_t:
            best_backend, best_cfg, best_t = (template.name, res.config,
                                              res.runtime_s)
    return best_backend, best_cfg, best_t, candidates


def select(
    graph: Graph,
    tuner: Optional[Tuner] = None,
    chip: hw.Chip = hw.TPU_V5E,
    dtype: str = "bfloat16",
    third_party: bool = True,
    model_parallel: int = 1,
) -> InferencePlan:
    """Build the inference plan for `graph`.

    `model_parallel` > 1 opens the LAYOUT axis of the race: nodes whose
    stage-qualified role appears in `costmodel.MATMUL_LAYOUT_ROLES` are
    additionally raced model-parallel over that many devices — the
    backend race re-run at the per-device shard shape, plus the price of
    the collective the layout implies (all-reduce for row-parallel roles,
    logits all-gather for lm_head, none for column-parallel) — and the
    winning layout lands on the choice's `layout` field next to the
    backend.  Shard dims that don't divide `model_parallel` keep the
    replicated layout (no illegal candidate is ever raced)."""
    tuner = tuner or Tuner(chip=chip)
    plan = InferencePlan(graph.name, chip.name)

    for node in graph.toposort():
        if node.op not in TUNABLE_OPS:
            continue
        op = op_desc_of(graph, node, dtype)
        if op is None:
            continue

        best_backend, best_cfg, best_t, candidates = _race_backends(
            op, tuner, chip, third_party)
        assert best_backend is not None, f"no backend for {node.name}"
        choice = OpChoice(best_backend, best_cfg, best_t, candidates)

        role = node.name.rsplit(".", 1)[-1]
        sharded = costmodel.sharded_op_desc(op, role, model_parallel)
        if sharded is not None:
            mp_backend, mp_cfg, mp_t, mp_cands = _race_backends(
                sharded, tuner, chip, third_party)
            mp_t += costmodel.layout_collective_time(op, role,
                                                     model_parallel, chip)
            choice.layout_candidates = {"replicated": best_t,
                                        "model_parallel": mp_t}
            if mp_backend is not None and mp_t < best_t:
                choice = OpChoice(mp_backend, mp_cfg, mp_t, mp_cands,
                                  layout="model_parallel",
                                  layout_candidates=choice.layout_candidates)
        plan.choices[node.name] = choice

    return plan
