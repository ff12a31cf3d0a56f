"""Work counts that do not depend on how the program does the work."""

from __future__ import annotations

from typing import Iterable, Tuple


def inv_work(blocks: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """FLOPs and HBM bytes of inverting ``count`` blocks ``n x n`` for
    each ``(n, count)``: ``n^3`` multiply-adds (2 n^3 FLOPs) per block,
    and reading each float32 factor block and writing its inverse
    (8 n^2 bytes). Any method needs at least this much; iterations of a
    particular method (Newton-Schulz, Taylor) are not counted."""
    flops = sum(2.0 * n ** 3 * c for n, c in blocks)
    nbytes = sum(8.0 * n ** 2 * c for n, c in blocks)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict,
               chips: int = 1) -> float:
    """The roofline's least time when ``chips`` share the work evenly."""
    return max(flops / (chips * peaks["bf16_flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))
