"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch, mesh), in seconds (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs_global / (chips * PEAK_FLOPS)
    memory     = HLO_bytes_global / (chips * HBM_BW)
    collective = collective_bytes_global / (chips * LINK_BW)

Sources: ``compiled.cost_analysis()`` reports the *per-device* (SPMD
partitioned) module; global = per-device * chips, so the chips factor
cancels and each term is simply per-device quantity / per-chip rate.
Collective bytes are not in cost_analysis: we parse the optimized HLO
and sum **operand** sizes of every collective op (the payload a chip
puts on the wire; all-gather output counts its *input* operands times
(group-1)/group under ring scheduling — we report raw operand bytes as
the spec'd metric and keep scheduling factors out).

Hardware constants: one TPU v5e chip — 197 TFLOP/s bf16, 819 GB/s
HBM, ~50 GB/s/link ICI. They describe the v5e that the dry run models
on CPU stand-in devices, and nothing else: :func:`analyze` refuses a
real device of another kind (:func:`check_device`).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import jax

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "all-to-all", "collective-permute")

# shape token like f32[256,1024]{1,0} or bf16[8,128]
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind *operand* bytes in a (per-device) HLO module.

    Post-optimization HLO prints operands without shapes, so operand
    bytes are reconstructed from the op's output shape(s) and group
    size g (``replica_groups=[n_groups, g]``):

        all-reduce / all-to-all / collective-permute: operand == output
        all-gather:      operand == output / g
        reduce-scatter:  operand == output * g

    Async ``-start`` forms output a (operand, result) tuple — the last
    shape token is the result buffer; ``-done`` lines are skipped so
    pairs count once.
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        for kind in _COLLECTIVES:
            m = re.search(rf"= .*? {kind}(-start)?\(", line)
            if m is None or f"{kind}-done" in line:
                continue
            lhs_text = line[line.find("=") + 1: m.end()]
            shapes = _SHAPE_RE.findall(lhs_text)
            if not shapes:
                continue
            if m.group(1):                     # -start: (operand, result)
                shapes = shapes[-1:]
            size = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
            g = 1
            gm = _GROUPS_RE.search(line)
            if gm:
                g = max(int(gm.group(2)), 1)
            if kind == "all-gather":
                size = size // g
            elif kind == "reduce-scatter":
                size = size * g
            out[kind] += size
            break
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    coll_breakdown: Dict[str, int]
    peak_hbm_per_dev: Optional[float]   # from memory_analysis
    chips: int
    raw_flops_per_dev: float = 0.0      # uncorrected cost_analysis
    raw_bytes_per_dev: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_dev / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Roofline step time (no-overlap upper bound is the sum; the
        classical roofline bound is the max — report max as 'bound')."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_json(self) -> dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "coll_breakdown": self.coll_breakdown,
            "peak_hbm_per_dev": self.peak_hbm_per_dev,
            "chips": self.chips,
            "raw_flops_per_dev": self.raw_flops_per_dev,
            "raw_bytes_per_dev": self.raw_bytes_per_dev,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
        }


def check_device(device) -> None:
    """Raise unless the peaks above describe ``device``: a CPU stand-in
    of the dry run, or a TPU v5e (``device_kind`` "TPU v5 lite")."""
    if device.platform == "cpu":
        return
    kind = device.device_kind.lower()
    if "v5 lite" not in kind and "v5e" not in kind:
        raise ValueError(
            f"roofline peaks are the TPU v5e's; refusing device kind "
            f"{device.device_kind!r}")


def analyze(lowered, compiled, chips: int) -> Roofline:
    """Roofline terms from the compiled per-device module.

    FLOPs/bytes/collectives come from the trip-count-aware HLO walk
    (launch/hlo_analysis.py) — XLA's own cost_analysis counts scan
    bodies once and is recorded only as ``raw_*`` for reference.
    """
    from repro.launch import hlo_analysis

    check_device(jax.devices()[0])
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mc = hlo_analysis.analyze_text(compiled.as_text())
    peak = None
    try:
        ma = compiled.memory_analysis()
        peak = float(
            ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        pass
    return Roofline(
        flops_per_dev=mc.flops,
        bytes_per_dev=mc.traffic_bytes,
        coll_bytes_per_dev=mc.coll_bytes,
        coll_breakdown={k: int(v) for k, v in mc.coll.items()},
        peak_hbm_per_dev=peak,
        chips=chips,
        raw_flops_per_dev=float(cost.get("flops", 0.0)),
        raw_bytes_per_dev=float(cost.get("bytes accessed", 0.0)),
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D train / 2*N*D inference (active params for
    MoE), D = tokens processed in the step."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d
    d = shape.global_batch * 1
    return 2.0 * n * d
