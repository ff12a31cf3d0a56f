"""Work counts of latent attention and of the held experts' grouped
matmuls, from a configuration's sizes (``reference/moonlight.sizes``),
whatever implements the work."""

from __future__ import annotations

from typing import Tuple


def mla_attention_flops(s: dict, rows: int, seq: int) -> float:
    """FLOPs of causal MLA's score and value products, forward and
    backward, for ``rows`` rows of ``seq`` tokens over all layers: the
    query at position ``t`` meets ``t + 1`` keys, ``2 (t + 1) h (dqk +
    dv)`` forward, tripled for the backward. Recomputation is not
    counted."""
    dqk = s["qk_nope_dim"] + s["qk_rope_dim"]
    per_row = seq * (seq + 1) * s["n_heads"] * (dqk + s["v_head_dim"])
    return 3.0 * rows * per_row * s["n_layers"]


def expert_work(s: dict, tokens: int) -> Tuple[float, float]:
    """FLOPs and HBM bytes of the held experts' gate, up and down
    products, forward and backward, over ``tokens`` tokens in every MoE
    layer, with ``top_k * experts_held / n_experts`` experts here per
    token (the expectation under routing): ``2 d f`` per product and row
    forward, tripled for the backward; bytes, bf16: each product's
    weight read once forward and twice backward, and its rows read and
    written, forward and backward."""
    d, f = s["d_model"], s["d_expert"]
    L = s["n_layers"] - s["n_dense_layers"]
    rows = tokens * s["top_k"] * s["experts_held"] / s["n_experts"]
    flops = 3 * 3 * 2.0 * d * f * rows * L
    weights = 3 * 3 * s["experts_held"] * d * f * 2.0 * L
    acts = 3 * 3 * rows * (d + f) * 2.0 * L
    return flops, weights + acts
