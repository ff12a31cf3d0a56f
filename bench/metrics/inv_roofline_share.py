"""INV's least time over its measured time per refresh. The least time is
the larger of (FLOPs / bf16 peak) and (bytes / HBM peak) for ``n^3``
multiply-adds per ``n x n`` block and one float32 read of each factor
block and write of its inverse (``counts.inv_work``), shared by the chips
when the refresh is distributed."""

import counts

#: the refresh program's name in the trace
MODULE = r"^jit__lambda\b"


def read(r):
    tr = r.trace
    s = tr.module_s(MODULE) if tr is not None else None
    if s is None or not r.window.inv_calls:
        return None
    flops, nbytes = counts.inv_work(r.inv_blocks)
    least = counts.least_time(flops, nbytes, r.peaks,
                              r.chips if r.dist_inv else 1)
    return 100.0 * least / (s / r.window.inv_calls)
