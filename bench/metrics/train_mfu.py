"""Model FLOPs of the forward and backward passes (``6 N`` plus causal
attention per token; recomputation, SU and INV not counted) times the
traced window's tokens per second, over chips times the bf16 peak."""


def read(r):
    tr = r.trace
    if tr is None or tr.window_s <= 0:
        return None
    rate = r.window.tokens / tr.window_s
    return 100.0 * r.flops_per_token * rate / (
        r.chips * r.peaks["bf16_flops_per_s"])
