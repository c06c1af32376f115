"""Schedule templates (paper §2.2) — the TPU analogue of Halide schedules.

The paper's semi-automatic approach: "pre-defines one or more schedule
templates for a given algorithm, then exposes a set of tunable
hyper-parameters ... and finally exploits automated search in the tunable
parameter space".  A template here is a parameterized Pallas kernel: the
tunables are BlockSpec tile sizes, grid iteration order and unroll factors —
the TPU equivalents of the paper's CUDA thread-block dims (T_x,T_y,T_z) and
per-thread tiles (Tile_x,Tile_y,Tile_z,Tile_rz).

The CUDA validity constraint ("total threads per block <= 1024") becomes the
VMEM-residency constraint: every block the Pallas pipeline holds (inputs
and output, double-buffered) plus the kernel's scratch and f32 temporaries
must fit in the chip's `vmem_budget_bytes` — the same limit every
`pallas_call` hands the compiler (`kernels.tpu`), so what the search admits
is exactly what the compiler is told.  `Template.validate` enforces it; the
searches only ever propose valid configurations (§2.3 Step1 "any randomly
generated configuration will be verified first").
"""

from __future__ import annotations

import dataclasses
import itertools
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import hw


@dataclasses.dataclass(frozen=True)
class OpDesc:
    """Hardware-relevant description of one operator instance.

    kind: 'matmul' | 'conv2d' | 'attention' | 'attention_decode'
    dims: kind-specific dims dict (see the templates below)
    dtype: compute dtype
    """

    kind: str
    dims: Tuple[Tuple[str, int], ...]
    dtype: str = "bfloat16"
    activation: Optional[str] = None
    label: str = ""

    @staticmethod
    def make(kind: str, dims: Dict[str, int], dtype: str = "bfloat16",
             activation: Optional[str] = None, label: str = "") -> "OpDesc":
        return OpDesc(kind, tuple(sorted(dims.items())), dtype, activation, label)

    @property
    def d(self) -> Dict[str, int]:
        return dict(self.dims)

    def signature(self) -> str:
        return json.dumps(
            [self.kind, list(self.dims), self.dtype, self.activation], sort_keys=True
        )

    @staticmethod
    def matmul(m: int, n: int, k: int, dtype="bfloat16", activation=None, label="") -> "OpDesc":
        return OpDesc.make("matmul", {"m": m, "n": n, "k": k}, dtype, activation, label)

    @staticmethod
    def conv2d(n, h, w, cin, cout, kh, kw, stride=1, padding="SAME",
               dtype="bfloat16", activation=None, label="") -> "OpDesc":
        pad = 1 if padding == "SAME" else 0
        oh = h // stride if pad else (h - kh) // stride + 1
        ow = w // stride if pad else (w - kw) // stride + 1
        return OpDesc.make(
            "conv2d",
            {"n": n, "h": h, "w": w, "cin": cin, "cout": cout, "kh": kh,
             "kw": kw, "stride": stride, "pad": pad, "oh": oh, "ow": ow},
            dtype, activation, label)

    @staticmethod
    def attention(b, q, kv, heads, head_dim, dtype="bfloat16", label="",
                  kv_heads=None) -> "OpDesc":
        """`kv_heads` < `heads` (GQA) records the group size as dim `g`: the
        kernels stack a group's query heads into one tile of rows."""
        dims = {"b": b, "q": q, "kv": kv, "h": heads, "d": head_dim}
        if kv_heads and heads // kv_heads > 1:
            dims["g"] = heads // kv_heads
        return OpDesc.make("attention", dims, dtype, None, label)

    def gemm_view(self) -> Tuple[int, int, int]:
        """(M, N, K) of the underlying GEMM (implicit GEMM for conv)."""
        d = self.d
        if self.kind == "matmul":
            return d["m"], d["n"], d["k"]
        if self.kind == "conv2d":
            return d["n"] * d["oh"] * d["ow"], d["cout"], d["kh"] * d["kw"] * d["cin"]
        if self.kind == "attention":
            # dominant GEMM: (b*h) batched q x kv
            return d["b"] * d["h"] * d["q"], d["kv"], d["d"]
        raise ValueError(self.kind)

    def flops(self) -> float:
        d = self.d
        if self.kind == "attention":
            return 4.0 * d["b"] * d["h"] * d["q"] * d["kv"] * d["d"]
        m, n, k = self.gemm_view()
        return 2.0 * m * n * k

    def io_bytes(self) -> int:
        """Minimum HBM traffic: read inputs once + write output once."""
        d = self.d
        item = np.dtype(self.dtype).itemsize
        if self.kind == "matmul":
            return item * (d["m"] * d["k"] + d["k"] * d["n"] + d["m"] * d["n"])
        if self.kind == "conv2d":
            return item * (
                d["n"] * d["h"] * d["w"] * d["cin"]
                + d["kh"] * d["kw"] * d["cin"] * d["cout"]
                + d["n"] * d["oh"] * d["ow"] * d["cout"]
            )
        if self.kind == "attention":
            return item * (
                3 * d["b"] * d["q"] * d["h"] * d["d"]  # q + out (+v-ish)
                + 2 * d["b"] * d["kv"] * d["h"] * d["d"]
            )
        raise ValueError(self.kind)

    def arithmetic_intensity(self) -> float:
        return self.flops() / max(1, self.io_bytes())


Config = Dict[str, Any]


class Template:
    """Base schedule template: a named, finite tunable-parameter space."""

    name: str = "base"
    kinds: Tuple[str, ...] = ()

    def space(self, op: OpDesc) -> Dict[str, List[Any]]:
        raise NotImplementedError

    def validate(self, op: OpDesc, cfg: Config, chip: hw.Chip = hw.TPU_V5E) -> bool:
        raise NotImplementedError

    # ---- encoding helpers shared by GA / RL / random searches ----------
    def axes(self, op: OpDesc) -> List[Tuple[str, List[Any]]]:
        return sorted(self.space(op).items())

    def encode(self, op: OpDesc, cfg: Config) -> List[int]:
        return [choices.index(cfg[k]) for k, choices in self.axes(op)]

    def decode(self, op: OpDesc, vec: Sequence[int]) -> Config:
        return {k: choices[v % len(choices)] for (k, choices), v in zip(self.axes(op), vec)}

    def random_config(self, op: OpDesc, rng: np.random.Generator,
                      chip: hw.Chip = hw.TPU_V5E, max_tries: int = 200) -> Config:
        axes = self.axes(op)
        for _ in range(max_tries):
            cfg = {k: choices[rng.integers(len(choices))] for k, choices in axes}
            if self.validate(op, cfg, chip):
                return cfg
        # Fall back to the smallest (always-valid) config.
        cfg = {k: choices[0] for k, choices in axes}
        assert self.validate(op, cfg, chip), "template has no valid config"
        return cfg

    def enumerate_configs(self, op: OpDesc, chip: hw.Chip = hw.TPU_V5E):
        axes = self.axes(op)
        names = [k for k, _ in axes]
        for combo in itertools.product(*[c for _, c in axes]):
            cfg = dict(zip(names, combo))
            if self.validate(op, cfg, chip):
                yield cfg

    def space_size(self, op: OpDesc) -> int:
        return int(np.prod([len(c) for _, c in self.axes(op)]))


def _vmem_matmul_bytes(bm: int, bn: int, bk: int, dtype) -> int:
    """VMEM the matmul kernel (`kernels/matmul.py`) holds for one config."""
    item = np.dtype(dtype).itemsize
    return (2 * (bm * bk + bk * bn + bm * bn) * item  # A, B, out: 2 buffers
            + 2 * 32 * bn                # bias block: 2 buffers, one tile high
            + 2 * bm * bn * 4)           # f32 accumulator + one K step's dot


class MatmulTemplate(Template):
    """Tiled MXU matmul: grid (M/bm, N/bn, K/bk), f32 VMEM accumulator.

    Tunables:
      bm, bn, bk     block sizes (MXU-aligned choices only)
      order          'mn' or 'nm' grid-major order (affects reuse direction)
      k_unroll       inner-K unroll factor hint
    """

    name = "pallas_matmul"
    kinds = ("matmul",)

    BM = [8, 16, 32, 64, 128, 256, 512, 1024]
    BN = [128, 256, 512, 1024]
    BK = [128, 256, 512, 1024, 2048]

    def space(self, op: OpDesc) -> Dict[str, List[Any]]:
        m, n, k = op.gemm_view()
        return {
            "bm": [b for b in self.BM if b <= max(8, 2 * m)],
            "bn": [b for b in self.BN if b <= max(128, 2 * n)],
            "bk": [b for b in self.BK if b <= max(128, 2 * k)],
            "order": ["mn", "nm"],
            "k_unroll": [1, 2, 4],
        }

    def validate(self, op: OpDesc, cfg: Config, chip: hw.Chip = hw.TPU_V5E) -> bool:
        sub = chip.sublane(op.dtype)
        if cfg["bm"] % sub and cfg["bm"] > sub:
            return False  # large unaligned bm wastes sublanes; tiny m pads
        if cfg["bn"] % chip.lane or cfg["bk"] % chip.lane:
            return False
        need = _vmem_matmul_bytes(cfg["bm"], cfg["bn"], cfg["bk"], op.dtype)
        return need <= chip.vmem_budget_bytes


class Conv2dTemplate(MatmulTemplate):
    """Convolution as implicit GEMM (in-kernel im2col), the TPU-native
    rethink of the paper's direct-CUDA conv template: M = N*OH*OW,
    K = KH*KW*CIN, N = COUT.  Extra tunable `row_block` controls how many
    output rows share one halo load."""

    name = "pallas_conv2d"
    kinds = ("conv2d",)

    def space(self, op: OpDesc) -> Dict[str, List[Any]]:
        s = super().space(op)
        s["row_block"] = [1, 2, 4, 8]
        return s

    def validate(self, op: OpDesc, cfg: Config, chip: hw.Chip = hw.TPU_V5E) -> bool:
        if not super().validate(op, cfg, chip):
            return False
        d = op.d
        # halo rows must fit alongside the GEMM blocks
        item = np.dtype(op.dtype).itemsize
        halo = (cfg["row_block"] * d["stride"] + d["kh"]) * d["w"] * d["cin"] * item
        return halo + _vmem_matmul_bytes(cfg["bm"], cfg["bn"], cfg["bk"], op.dtype) \
            <= chip.vmem_budget_bytes


def _vmem_attention_bytes(rows: int, kv_rows: int, d: int, dtype,
                          chip: hw.Chip) -> int:
    """VMEM one flash-attention tile holds (`kernels/attention.py`): `rows`
    query rows (block_q x the GQA group for the paged prefill kernel)
    against `kv_rows` key rows.  The paged kernels read pool blocks no
    taller than the template's smallest block_kv, so this bounds them too."""
    item = np.dtype(dtype).itemsize
    hd = max(d, chip.lane)
    return (2 * 2 * rows * hd * item         # q and out blocks: 2 buffers
            + 2 * 2 * kv_rows * hd * item    # k and v blocks: 2 buffers
            + 2 * rows * chip.lane * 4       # running max + denominator
            + rows * hd * 4                  # f32 output accumulator
            + 2 * rows * max(kv_rows, chip.lane) * 4)  # f32 logits + probs


class AttentionTemplate(Template):
    """Flash-attention schedule: online-softmax over KV blocks.

    Tunables: block_q, block_kv sizes; whether the (b,h) grid axis is
    'arbitrary' (parallel) or the kv axis is innermost.  Serve-graph
    `prefill_chunk` ops (the segment-packed chunk lane of the unified
    serving step) additionally race `max_segments` — the packing width of
    the segmented kernel's block_q x max-segments grid, which the
    scheduler consumes as its per-step packing cap
    (`PlanRouter.chunk_segments`).
    """

    name = "pallas_attention"
    kinds = ("attention",)

    BQ = [128, 256, 512, 1024]
    BKV = [128, 256, 512, 1024, 2048]
    MAX_SEGMENTS = [1, 2, 4, 8]

    def space(self, op: OpDesc) -> Dict[str, List[Any]]:
        d = op.d
        s = {
            "block_q": [b for b in self.BQ if b <= max(128, d["q"])],
            "block_kv": [b for b in self.BKV if b <= max(128, d["kv"])],
        }
        if op.label.startswith("prefill_chunk"):
            # packing can't exceed one request per query row
            s["max_segments"] = [m for m in self.MAX_SEGMENTS
                                 if m <= max(1, d["q"])]
        return s

    def validate(self, op: OpDesc, cfg: Config, chip: hw.Chip = hw.TPU_V5E) -> bool:
        d = op.d
        rows = cfg["block_q"] * d.get("g", 1)
        need = _vmem_attention_bytes(rows, cfg["block_kv"], d["d"], op.dtype,
                                     chip)
        return need <= chip.vmem_budget_bytes


TEMPLATES: Dict[str, Template] = {
    t.name: t for t in (MatmulTemplate(), Conv2dTemplate(), AttentionTemplate())
}


def templates_for(op: OpDesc) -> List[Template]:
    return [t for t in TEMPLATES.values() if op.kind in t.kinds]
