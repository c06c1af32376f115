"""Compile-only rehearsal of a cell's two step programs for a described TPU.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [...]

No chip is attached: JAX's TPU compiler compiles the unified and the
decode-only step program of each configuration — the plan it selects, its
mesh, its slots, pool and context from the configuration file — for a
described v5e (`v5e:2x2`; a one-chip mesh takes its first device) and
prints `memory_analysis()` of each, per device.  This sizes slots, pool
and context before any chip run.  Nothing runs, so it gives no time.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

GiB = 2**30


def rehearse(name: str, topo, runtime=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro import hw
    from repro.distributed.sharding import (DEFAULT_RULES, params_shardings,
                                            prune_for_mesh)
    from repro.launch.steps import (jit_decode_only_step, jit_unified_step,
                                    paged_pool_sharding)
    from repro.models import build_model
    from repro.serve import PlanRouter
    from repro.serve.sampling import (segment_sampling_arrays,
                                      slot_sampling_arrays)

    cfg = run.load_config(name)
    cfg["runtime"].update(runtime or {})
    chip = hw.chip_for_kind(topo.devices[0].device_kind)
    mcfg = run.program_config(cfg)
    model = build_model(mcfg)
    rcfg = run.runtime_config(cfg)
    tp = cfg["mesh"]["model"]
    mesh = Mesh(np.asarray(topo.devices[:tp]).reshape(1, tp),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    router = PlanRouter(run.serve_plan(cfg, mcfg, rcfg, chip))
    rules = router.serve_rules(DEFAULT_RULES, mesh, mcfg, "decoder")
    p_shard = params_shardings(mesh, prune_for_mesh(rules, mesh),
                               model.logical_axes())
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, jnp.dtype(mcfg.dtype),
                                           sharding=sh), shapes, p_shard)
    kv = rcfg.kv_config()
    pool_sh = paged_pool_sharding(model, mesh, prune_for_mesh(rules, mesh))
    pool = jax.ShapeDtypeStruct(
        (mcfg.n_layers, kv.num_blocks, kv.block_size, mcfg.n_kv_heads,
         mcfg.hd), jnp.dtype(mcfg.dtype), sharding=pool_sh)
    slots, nbt = rcfg.max_slots, kv.max_blocks_per_seq
    segs = max(1, min(rcfg.chunk_segments,
                      router.chunk_segments(default=rcfg.chunk_segments)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    dsp, dks = map(like, slot_sampling_arrays([None] * slots))
    ssp, sks = map(like, segment_sampling_arrays([], segs))
    dec_attn, _ = router.attention_backend("decode")
    ch_attn, ch_conf = router.attention_backend("prefill_chunk")
    dec = (params, pool, pool, i32(slots, nbt), i32(slots), i32(slots, 1))
    progs = {
        "unified": (jit_unified_step(
            model, mesh, rules, decode_attn_backend=dec_attn,
            chunk_attn_backend=ch_attn, chunk_attn_config=ch_conf,
            decode_matmul_table=router.matmul_table("decode"),
            chunk_matmul_table=router.matmul_table("prefill_chunk")),
            dec + (i32(1, rcfg.chunk_width), i32(segs, nbt), i32(segs, 3),
                   dsp, dks, ssp, sks)),
        "decode_only": (jit_decode_only_step(
            model, mesh, rules, decode_attn_backend=dec_attn,
            decode_matmul_table=router.matmul_table("decode")),
            dec + (dsp, dks)),
    }
    print(f"{name}: {mcfg.n_layers}x{mcfg.d_model}, mesh 1x{tp} on "
          f"{topo.devices[0].device_kind}; slots {slots}, context "
          f"{rcfg.max_seq}, pool {kv.num_blocks} blocks of {kv.block_size}, "
          f"chunk {rcfg.chunk_width} x {segs} segments")
    print(f"  plan: {router.describe()}")
    with mesh:
        for label, (fn, args) in progs.items():
            comp = fn.lower(*args).compile()
            m = comp.memory_analysis()
            text = comp.as_text()
            coll = {c: text.count(f"{c}-start") + text.count(f" {c}(")
                    for c in ("all-reduce", "all-gather", "reduce-scatter")}
            print(f"  {label}: per device arguments "
                  f"{m.argument_size_in_bytes / GiB:.2f} GiB, outputs "
                  f"{m.output_size_in_bytes / GiB:.2f} GiB, aliased "
                  f"{m.alias_size_in_bytes / GiB:.2f} GiB, temporaries "
                  f"{m.temp_size_in_bytes / GiB:.2f} GiB; Pallas kernels "
                  f"{text.count('tpu_custom_call')}; collectives {coll}")


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies

    names = (argv if argv is not None else sys.argv[1:]) or [
        "qwen3-1.7b", "granite-3-8b-tp4"]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from repro import hw
    from repro.kernels import tpu
    chip = hw.chip_for_kind(topo.devices[0].device_kind)
    tpu.interpret = lambda: False
    tpu.target_chip = lambda: chip
    for name in names:
        rehearse(name, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
