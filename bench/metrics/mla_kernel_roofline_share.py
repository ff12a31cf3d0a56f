"""Causal MLA's score and value FLOPs at query/key width 192 and value
width 128, forward and backward (``moe_counts.mla_attention_flops``),
over the device time of the Pallas calls under the ``mla`` scope of the
train program (the flash kernel's forward, recomputed forward, dq and
dkv), against the bf16 peak."""

import moe_counts
import moe_scopes


def read(r):
    s = moe_scopes.seconds(r, "jit_train_step", "mla/kernel")
    if not s or not r.window.steps:
        return None
    t = r.cell.traffic
    sizes = r.cell.reference.sizes(r.cell.arch)
    flops = moe_counts.mla_attention_flops(sizes, t["batch"], t["seq"])
    return 100.0 * flops * r.window.steps / s / (
        r.chips * r.peaks["bf16_flops_per_s"])
