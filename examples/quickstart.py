"""WPK quickstart: the paper's Figure-1a pipeline on a small conv net.

    graph import -> graph optimization (§2.1) -> automated search (§2.3)
    -> system-level backend selection (§2.5) -> runtime engine

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core import (
    Engine,
    Graph,
    Tuner,
    default_registry,
    optimize_graph,
    select,
)


def build_graph() -> Graph:
    rng = np.random.default_rng(0)
    g = Graph("quickstart")
    x = g.add_input("x", (4, 3, 32, 32))
    w1 = g.add_constant("w1", rng.standard_normal((16, 3, 3, 3)).astype(np.float32) * 0.2)
    c1 = g.add_node("conv2d", [x, w1], (4, 16, 32, 32), {"stride": 1, "padding": "SAME"})
    sc = g.add_constant("sc", (rng.random(16) + 0.5).astype(np.float32))
    sh = g.add_constant("sh", rng.standard_normal(16).astype(np.float32) * 0.1)
    b1 = g.add_node("batch_norm", [c1, sc, sh], (4, 16, 32, 32))
    r1 = g.add_node("relu", [b1], (4, 16, 32, 32))
    d1 = g.add_node("dropout", [r1], (4, 16, 32, 32))   # removed at inference
    w2 = g.add_constant("w2", rng.standard_normal((32, 16, 3, 3)).astype(np.float32) * 0.2)
    c2 = g.add_node("conv2d", [d1, w2], (4, 32, 16, 16), {"stride": 2, "padding": "SAME"})
    g2 = g.add_node("gelu", [c2], (4, 32, 16, 16))
    gp = g.add_node("global_avg_pool", [g2], (4, 32))
    wf = g.add_constant("wf", rng.standard_normal((32, 10)).astype(np.float32) * 0.3)
    out = g.add_node("matmul", [gp, wf], (4, 10))
    g.set_outputs([out])
    return g


def main() -> None:
    g = build_graph()
    print(f"imported   : {g}")

    gopt = optimize_graph(g)                       # §2.1
    print(f"optimized  : {gopt}")

    tuner = Tuner(methods=("genetic",))            # §2.3 (add 'rl' for §2.4)
    plan = select(gopt, tuner=tuner)               # §2.2 + §2.5
    print(f"plan       : {plan.backend_histogram()}, "
          f"modeled {plan.total_modeled_time_s() * 1e6:.1f} us/batch on TPU v5e")
    for name, choice in plan.choices.items():
        print(f"  {name:24s} -> {choice.backend:16s} "
              f"{choice.modeled_time_s * 1e6:7.2f} us  "
              f"(candidates: {({k: round(v * 1e6, 2) for k, v in choice.candidates.items()})})")

    engine = Engine(gopt, plan, default_registry())
    x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 3, 32, 32)).astype(np.float32))
    err = engine.verify_against_reference(x)
    print(f"engine     : optimized plan == reference graph (max err {err:.2e})")


if __name__ == "__main__":
    main()
