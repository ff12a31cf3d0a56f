"""The held experts' least time over their measured time per step. The
least time is the larger of (FLOPs / bf16 peak) and (bytes / HBM peak)
for the gate, up and down products of the rows routed here at the
expected share, forward and backward (``moe_counts.expert_work``); the
measured time is the train program's ``moe_experts`` scope.

The rows are the expectation under routing, ``top_k * experts_held /
n_experts`` per token, not the rows the run routed here: the program
counts those only on its statistics steps (``moe_held_tokens``), which no
reader is handed. A window's held rows differ from the expectation by
the topics its rows draw (one 8k row holds one topic's 512 ids, whose
tokens route alike), so the share moves with the seed by as much as the
held load does (PERF.md §3)."""

import counts
import moe_counts
import moe_scopes


def read(r):
    s = moe_scopes.seconds(r, "jit_train_step", "moe_experts")
    if not s or not r.window.steps:
        return None
    t = r.cell.traffic
    sizes = r.cell.reference.sizes(r.cell.arch)
    flops, nbytes = moe_counts.expert_work(sizes, t["batch"] * t["seq"])
    least = counts.least_time(flops, nbytes, r.peaks, r.chips)
    return 100.0 * least / (s / r.window.steps)
