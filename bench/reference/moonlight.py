"""Plain reference of Moonlight-16B-A3B (the DeepSeek-V3 architecture) at
one chip's expert share, trained by the K-FAC step that the benchmark
times, written from the published architecture and the optimizer's
equations alone.

Everything is float32 ``jax.numpy`` at ``highest`` matmul precision. The
model: token embedding; ``n_dense_layers`` leading layers of latent
attention (MLA) and a SwiGLU MLP of width ``d_ff``; then MoE layers of
MLA, shared experts (one SwiGLU MLP of ``n_shared_experts * d_expert``)
and the held routed experts; a final RMSNorm and an untied head over the
vocabulary slice; the loss is the mean next-token cross-entropy over the
slice. Norms scale by ``1 + w`` (w zero at init: the published ones);
the latent's norm has eps 1e-6 (the published module default), the
others ``norm_eps``.

MLA in its training form: ``q = x Wq`` per head ``[nope | rope]``;
``[c | k_rope] = x Wkv_a``; ``c`` normed; ``[k_nope | v] = c Wkv_b`` per
head; the 64 rope channels of q and of the one shared key head rotate by
the half split (the published code de-interleaves them first, the same
model with the projections' rope columns permuted; the program uses the
same half split). Scores scale by ``(nope + rope) ** -0.5``, causal
softmax, computed in blocks of queries so that 8k tokens fit.

Routing over all ``n_experts``: float32 logits, sigmoid scores, the top
``top_k`` by score plus the correction bias (a leaf held at zero, with
no gradient) chosen, weights the unbiased scores normalized over the
``top_k`` and scaled by ``routed_scale``. Each held expert
(``expert_first`` .. ``+ experts_held``) is applied to every token and
its output multiplied by the token's weight for it (zero where the token
did not choose it): a formulation apart from the program's sort and
grouped matmuls. The experts another chip holds add nothing here, as in
the program.

K-FAC as ``qwen_dense`` states it, with these factored linears: MLA's
``wq``, ``wkv_a`` (sharing the A of ``wq``), ``wkv_b`` (A of the normed
latent) and ``wo``; the dense MLP and the shared experts (``wu`` sharing
the A of ``wg``); and per held expert ``e`` its ``wg``, ``wu`` and ``wd``
with ``A_e = sum_{t->e} x x^T / n_e`` over the ``n_e`` tokens routed to
it in the statistics batch and ``G_e = sum_{t->e} g g^T``.

Sizes come from ``arch`` where it has the key, else from this
configuration's file (``bench/configs/moonlight-16b-a3b.json``).

This module imports nothing of the system under test.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, os.pardir, "configs", "moonlight-16b-a3b.json")


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_qd = _load(os.path.join(HERE, "qwen_dense.py"))
F32 = jnp.float32
Prec = _qd.Prec
key_for = _qd.key_for
block_size_for = _qd.block_size_for
path_of = _qd.path_of
leaves_by_path = _qd.leaves_by_path
_pad_blocks = _qd._pad_blocks
_rms = _qd._rms
_rope = _qd._rope

#: latent norm eps (DeepseekV3RMSNorm's default)
LATENT_EPS = 1e-6
#: queries per attention block
Q_BLOCK = 1024


def sizes(arch: dict) -> dict:
    """Every size the model needs: ``arch``'s where it has the key, else
    the configuration file's."""
    with open(CONFIG) as f:
        c = json.load(f)
    out = {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"], "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_dim": c["qk_nope_head_dim"],
        "qk_rope_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "n_experts": c["router_width"], "experts_held": c["n_routed_experts"],
        "expert_first": c["expert_first"],
        "top_k": c["num_experts_per_tok"],
        "n_shared_experts": c["n_shared_experts"],
        "d_expert": c["moe_intermediate_size"],
        "n_dense_layers": c["first_k_dense_replace"],
        "routed_scale": float(c["routed_scaling_factor"]),
    }
    out.update(arch)
    return out


def linears(arch: dict) -> Dict[str, Tuple[int, int, Tuple[int, ...], str]]:
    """Every factored linear: path -> (d_in, d_out, stack, the linear
    whose input Gram it shares, or None)."""
    s = sizes(arch)
    d, h, r = s["d_model"], s["n_heads"], s["kv_lora_rank"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    nd, L = s["n_dense_layers"], s["n_layers"] - s["n_dense_layers"]
    fs, fe = s["n_shared_experts"] * s["d_expert"], s["d_expert"]
    out = {}

    def mla(pre, stack):
        out[f"{pre}/mla/wq"] = (d, h * qk, stack, None)
        out[f"{pre}/mla/wkv_a"] = (d, r + s["qk_rope_dim"], stack,
                                   f"{pre}/mla/wq")
        out[f"{pre}/mla/wkv_b"] = (r, h * (s["qk_nope_dim"]
                                          + s["v_head_dim"]), stack, None)
        out[f"{pre}/mla/wo"] = (h * s["v_head_dim"], d, stack, None)

    def mlp(pre, stack, f):
        out[f"{pre}/wg"] = (d, f, stack, None)
        out[f"{pre}/wu"] = (d, f, stack, f"{pre}/wg")
        out[f"{pre}/wd"] = (f, d, stack, None)

    if nd:
        mla("dense_layers", (nd,))
        mlp("dense_layers/mlp", (nd,), s["d_ff"])
    mla("layers", (L,))
    mlp("layers/shared", (L,), fs)
    mlp("layers/moe", (L, s["experts_held"]), fe)
    return out


def factor_blocks(arch: dict, cap: int) -> Dict[Tuple[str, str], Tuple]:
    """``(linear, "A"|"G") -> (blocks per layer, bs)``: a factor's
    blocks over all its stacked layers (and experts), divided by
    ``n_layers``, so that ``n_layers`` times it is the factor's count
    (the harness multiplies by ``n_layers``)."""
    n = sizes(arch)["n_layers"]
    out = {}
    for name, (din, dout, stack, share) in linears(arch).items():
        per = 1
        for x in stack:
            per *= x
        for side, dim in (("A", din), ("G", dout)):
            if side == "A" and share is not None:
                continue
            bs = block_size_for(dim, cap)
            out[(name, side)] = (per * -(-dim // bs) / n, bs)
    return out


def active_matmul_params(arch: dict) -> float:
    """Matmul weights a token meets: the head over the slice, the dense
    layers, MLA, the router and the shared experts of every MoE layer,
    and the routed experts at ``top_k * experts_held / n_experts`` per
    token and layer (the expectation under routing)."""
    s = sizes(arch)
    lin = linears(arch)
    d = s["d_model"]
    total = d * s["vocab"]
    for name, (din, dout, stack, _) in lin.items():
        n = 1
        for x in stack:
            n *= x
        if name.startswith("layers/moe/"):
            n = stack[0] * s["top_k"] * s["experts_held"] / s["n_experts"]
        total += n * din * dout
    total += (s["n_layers"] - s["n_dense_layers"]) * d * s["n_experts"]
    return total


def train_flops_per_token(arch: dict, seq: int) -> float:
    """FLOPs of one forward and backward pass per token: ``6 N`` over
    the active matmul weights (:func:`active_matmul_params`) plus causal
    MLA, whose query at position ``t`` scores ``t + 1`` keys at the
    query/key width and mixes as many values at the value width: forward
    ``2 (t + 1) h (dqk + dv)`` per layer, averaged over ``t`` and
    tripled for the backward. Recomputation is not counted."""
    s = sizes(arch)
    dqk = s["qk_nope_dim"] + s["qk_rope_dim"]
    attn = 3 * (seq + 1) * s["n_heads"] * (dqk + s["v_head_dim"]) \
        * s["n_layers"]
    return 6.0 * active_matmul_params(arch) + attn


# -- weights ---------------------------------------------------------------


def init(arch: dict, key) -> dict:
    """The program's weights from ``key``, leaf path by leaf path:
    embedding N(0, 0.02^2); each matmul weight N(0, 1/d_in); norms, and
    the routers' correction bias, zero. ``split(key, 8)``: embedding from
    the first, the head from the second, the MoE stack from
    ``split(fourth, L)``, the dense stack from ``split(sixth, n)``; per
    layer ``split(., 3)``: MLA (``split(., 4)``: wq, wkv_a, wkv_b, wo),
    the MLP, or the experts (``split(., 2)``: router, then expert ``e``
    from ``split(fold_in(second, e), 3)``: wg, wu, wd), then the shared
    experts (``split(., 3)``)."""
    s = sizes(arch)
    d, h, r = s["d_model"], s["n_heads"], s["kv_lora_rank"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    dv, fe = s["v_head_dim"], s["d_expert"]
    normal = jax.random.normal
    ks = jax.random.split(key, 8)

    def w(k, din, dout):
        return normal(k, (din, dout), F32) * din ** -0.5

    def mla(k):
        a = jax.random.split(k, 4)
        return {"wq": w(a[0], d, h * qk),
                "wkv_a": w(a[1], d, r + s["qk_rope_dim"]),
                "kv_norm": jnp.zeros((r,), F32),
                "wkv_b": w(a[2], r, h * (s["qk_nope_dim"] + dv)),
                "wo": w(a[3], h * dv, d)}

    def mlp(k, f):
        m = jax.random.split(k, 3)
        return {"wg": w(m[0], d, f), "wu": w(m[1], d, f),
                "wd": w(m[2], f, d)}

    def dense_layer(k):
        la, lm, _ = jax.random.split(k, 3)
        return {"ln1": jnp.zeros((d,), F32), "ln2": jnp.zeros((d,), F32),
                "mla": mla(la), "mlp": mlp(lm, s["d_ff"])}

    def moe_layer(k):
        la, lm, ls = jax.random.split(k, 3)
        kr, ke = jax.random.split(lm)

        def expert(e):
            m = jax.random.split(jax.random.fold_in(ke, e), 3)
            return w(m[0], d, fe), w(m[1], d, fe), w(m[2], fe, d)

        wg, wu, wd = jax.vmap(expert)(
            s["expert_first"] + jnp.arange(s["experts_held"]))
        return {"ln1": jnp.zeros((d,), F32), "ln2": jnp.zeros((d,), F32),
                "mla": mla(la),
                "moe": {"router": w(kr, d, s["n_experts"]),
                        "bias": jnp.zeros((s["n_experts"],), F32),
                        "wg": wg, "wu": wu, "wd": wd},
                "shared": mlp(ls, s["n_shared_experts"] * fe)}

    L = s["n_layers"] - s["n_dense_layers"]
    out = {
        "embed": normal(ks[0], (s["vocab"], d), F32) * 0.02,
        "final_norm": jnp.zeros((d,), F32),
        "lm_head": w(ks[1], d, s["vocab"]),
        "layers": jax.vmap(moe_layer)(jax.random.split(ks[3], L)),
    }
    if s["n_dense_layers"]:
        out["dense_layers"] = jax.vmap(dense_layer)(
            jax.random.split(ks[5], s["n_dense_layers"]))
    return out


# -- model -----------------------------------------------------------------


def _gram(a, bs, prec):
    """``sum_t a_t a_t^T`` per diagonal block: (..., T, d) ->
    (..., nb, bs, bs)."""
    a = _pad_blocks(a, -1, bs)
    a = a.reshape(a.shape[:-1] + (-1, bs))
    return prec.mm("...tib,...tic->...ibc", a, a, side="kfac")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gtap(y, z, bs, prec):
    """Identity on ``y`` (..., T, d); the cotangent of ``z`` is the blocked
    Gram of ``dL/dy`` over the tokens, per leading index."""
    del z
    return y


def _gtap_fwd(y, z, bs, prec):
    del z
    return y, None


def _gtap_bwd(bs, prec, _, ct):
    return ct, _gram(ct, bs, prec)


_gtap.defvjp(_gtap_fwd, _gtap_bwd)


class Model:
    def __init__(self, arch: dict, cap: int, prec: Prec):
        self.arch, self.cap, self.prec = arch, cap, prec
        self.s = sizes(arch)
        self.lin = linears(arch)
        self.blocks = factor_blocks(arch, cap)

    def _bs(self, name, side):
        return self.blocks[(name, side)][1]

    def _linear(self, x, w, name, z, acts, spec="td,df->tf", mask=None):
        """``x W`` with the A Gram of ``x`` added into ``acts`` (when
        collecting; over the tokens ``mask`` keeps, per expert) and the
        G Gram hooked through ``z``."""
        if acts is not None and (name, "A") in self.blocks:
            a = x if mask is None else \
                (x if x.ndim == 3 else x[None]) * mask[..., None]
            acts[name] = _gram(a, self._bs(name, "A"), self.prec)
        y = self.prec.mm(spec, x, w)
        if z is not None and name in z:
            y = _gtap(y, z[name], self._bs(name, "G"), self.prec)
        return y

    def _attention(self, q, k, v):
        """Causal softmax attention in blocks of queries: q/k (T, h, dqk),
        v (T, h, dv) -> (T, h, dv)."""
        T = q.shape[0]
        blk = Q_BLOCK if T % Q_BLOCK == 0 else T
        scale = q.shape[-1] ** -0.5
        keys = jnp.arange(T)

        @jax.checkpoint
        def one(xs):
            i, qi = xs
            s = self.prec.mm("thd,shd->hts", qi, k) * scale
            rows = i * blk + jnp.arange(blk)
            s = jnp.where(rows[:, None] >= keys[None, :], s, -1e30)
            return self.prec.mm("hts,she->the", jax.nn.softmax(s, -1), v)

        out = jax.lax.map(one, (jnp.arange(T // blk),
                                q.reshape(T // blk, blk, *q.shape[1:])))
        return out.reshape(T, *out.shape[2:])

    def _mla(self, x, p, pre, z, acts):
        s = self.s
        T = x.shape[0]
        h, r = s["n_heads"], s["kv_lora_rank"]
        nope, rope, dv = s["qk_nope_dim"], s["qk_rope_dim"], s["v_head_dim"]
        pm = p["mla"]
        hin = _rms(x, p["ln1"], s["norm_eps"])
        q = self._linear(hin, pm["wq"], f"{pre}/mla/wq", z, acts)
        kv_a = self._linear(hin, pm["wkv_a"], f"{pre}/mla/wkv_a", z, acts)
        c = _rms(kv_a[:, :r], pm["kv_norm"], LATENT_EPS)
        kv = self._linear(c, pm["wkv_b"], f"{pre}/mla/wkv_b", z, acts)
        q = q.reshape(T, h, nope + rope)
        kv = kv.reshape(T, h, nope + dv)
        pos = jnp.arange(T, dtype=F32)
        q_pe = _rope(q[..., nope:], pos, s["rope_theta"])
        k_pe = _rope(kv_a[:, None, r:], pos, s["rope_theta"])
        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_pe, (T, h, rope))], -1)
        o = self._attention(q, k, kv[..., nope:])
        return x + self._linear(o.reshape(T, h * dv), pm["wo"],
                                f"{pre}/mla/wo", z, acts)

    def _mlp(self, x, p, pre, z, acts):
        g = self._linear(x, p["wg"], f"{pre}/wg", z, acts)
        u = self._linear(x, p["wu"], f"{pre}/wu", z, acts)
        return self._linear(jax.nn.silu(g) * u, p["wd"], f"{pre}/wd", z,
                            acts)

    def route(self, x, pm):
        """(weights (T, held) of the held experts, zero where a token did
        not choose one) of the normed input ``x``."""
        s = self.s
        logits = self.prec.mm("td,de->te", x, pm["router"])
        scores = jax.nn.sigmoid(logits)
        _, eid = jax.lax.top_k(scores + jax.lax.stop_gradient(pm["bias"]),
                               s["top_k"])
        w = jnp.take_along_axis(scores, eid, -1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * s["routed_scale"]
        held = s["expert_first"] + jnp.arange(s["experts_held"])
        chosen = eid[:, :, None] == held[None, None, :]        # (T, k, H)
        return jnp.sum(jnp.where(chosen, w[..., None], 0.0), 1), \
            jnp.any(chosen, 1)

    def _experts(self, x, pm, z, acts, counts):
        """The held experts on every token, each output weighted by the
        token's routing weight for it."""
        weight, mask = self.route(x, pm)
        mask_e = mask.T.astype(F32)                             # (H, T)
        if counts is not None:
            counts.append(jnp.sum(mask_e, -1))
        g = self._linear(x, pm["wg"], "layers/moe/wg", z, acts,
                         "td,edf->etf", mask_e)
        u = self._linear(x, pm["wu"], "layers/moe/wu", z, acts,
                         "td,edf->etf")
        y = self._linear(jax.nn.silu(g) * u, pm["wd"], "layers/moe/wd", z,
                         acts, "etf,efd->etd", mask_e)
        return jnp.einsum("etd,te->td", y, weight, precision=_qd.HIGHEST)

    def row_loss(self, params, tokens, zs, n_total, collect=False):
        """Summed next-token NLL of one row over ``n_total``; with
        ``collect`` the A Gram sums over the row's tokens (per expert:
        over its routed tokens) and the held experts' token counts."""
        s = self.s
        x = params["embed"][tokens]
        nd = s["n_dense_layers"]
        acts_all = {}

        def dense_body(x, xs):
            p, z = xs
            acts = {} if collect else None
            x = self._mla(x, p, "dense_layers", z, acts)
            hin = _rms(x, p["ln2"], s["norm_eps"])
            x = x + self._mlp(hin, p["mlp"], "dense_layers/mlp", z, acts)
            return x, acts or {}

        def moe_body(x, xs):
            p, z = xs
            acts = {} if collect else None
            counts = [] if collect else None
            x = self._mla(x, p, "layers", z, acts)
            hin = _rms(x, p["ln2"], s["norm_eps"])
            routed = self._experts(hin, p["moe"], z, acts, counts)
            shared = self._mlp(hin, p["shared"], "layers/shared", z, acts)
            if collect:
                acts["layers/moe/counts"] = counts[0]
            return x + (routed + shared), acts or {}

        if nd:
            x, a = jax.lax.scan(jax.checkpoint(dense_body), x,
                                (params["dense_layers"],
                                 {k: v for k, v in zs.items()
                                  if k.startswith("dense_layers/")}))
            acts_all.update(a)
        x, a = jax.lax.scan(jax.checkpoint(moe_body), x,
                            (params["layers"],
                             {k: v for k, v in zs.items()
                              if k.startswith("layers/")}))
        acts_all.update(a)
        x = _rms(x, params["final_norm"], s["norm_eps"])

        @jax.checkpoint
        def nll(x, head):
            logits = self.prec.mm("td,dv->tv", x[:-1], head)
            gold = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

        return nll(x, params["lm_head"]) / n_total, acts_all

    def _stacked(self, side):
        out = {}
        for (name, sd), (nb, bs) in self.blocks.items():
            if sd == side:
                stack = self.lin[name][2]
                n = -(-self.lin[name][0 if side == "A" else 1] // bs)
                out[name] = jnp.zeros(stack + (n, bs, bs), F32)
        return out

    def zero_taps(self):
        return self._stacked("G")

    def stats(self, params, tokens):
        """SU pass over a (B, T) batch: (A Grams, G Grams, mean loss);
        the held experts' A over their own token counts."""
        B, T = tokens.shape
        n_total = B * (T - 1)
        zs = self.zero_taps()
        step = jax.value_and_grad(
            lambda z, tok: self.row_loss(params, tok, z, n_total, True),
            has_aux=True)
        L = self.s["n_layers"] - self.s["n_dense_layers"]

        def row(carry, tok):
            (loss, acts), gz = step(zs, tok)
            a, g, l = carry
            return (jax.tree.map(jnp.add, a, acts),
                    jax.tree.map(jnp.add, g, gz), l + loss), None

        a0 = self._stacked("A")
        a0["layers/moe/counts"] = jnp.zeros((L, self.s["experts_held"]),
                                            F32)
        (a, g, loss), _ = jax.lax.scan(row, (a0, zs, jnp.zeros((), F32)),
                                       tokens)
        n_e = jnp.maximum(a.pop("layers/moe/counts"), 1.0)
        a = {k: v / (n_e[..., None, None, None]
                     if k.startswith("layers/moe/") else B * T)
             for k, v in a.items()}
        return a, g, loss

    def grads(self, params, tokens):
        """(mean loss, gradient) over a (B, T) batch."""
        B, T = tokens.shape
        n_total = B * (T - 1)
        zs = {}
        step = jax.value_and_grad(
            lambda p, tok: self.row_loss(p, tok, zs, n_total)[0])

        def row(carry, tok):
            loss, g = step(params, tok)
            gs, l = carry
            return (jax.tree.map(jnp.add, gs, g), l + loss), None

        g0 = jax.tree.map(jnp.zeros_like, params)
        (g, loss), _ = jax.lax.scan(row, (g0, jnp.zeros((), F32)), tokens)
        return loss, g


# -- K-FAC -----------------------------------------------------------------


class KFAC:
    """The trainer's K-FAC step in plain arithmetic (``qwen_dense``'s,
    over this model's factored linears); the momentum is kept for the
    factored weights and Adam's moments for the rest, as in the program,
    so that the reference fits one chip beside its model."""

    def __init__(self, model: Model, hp: dict):
        self.m, self.hp = model, hp
        self.factored = set(model.lin)

    def init_state(self, params):
        factors, inverses = {}, {}
        for (name, side), (_, bs) in self.m.blocks.items():
            stack = self.m.lin[name][2]
            n = -(-self.m.lin[name][0 if side == "A" else 1] // bs)
            factors.setdefault(name, {})[side] = \
                jnp.zeros(stack + (n, bs, bs), F32)
            inverses.setdefault(name, {})[side + "_inv"] = \
                jnp.broadcast_to(jnp.eye(bs, dtype=F32),
                                 stack + (n, bs, bs))

        def moments(factored):
            return jax.tree_util.tree_map_with_path(
                lambda p, x: jnp.zeros_like(x)
                if (path_of(p) in self.factored) == factored
                else jnp.zeros((0,), F32), params)

        return {"step": 0, "factors": factors, "inverses": inverses,
                "momentum": moments(True), "adam_mu": moments(False),
                "adam_nu": moments(False)}

    def update_factors(self, factors, a, g):
        e = self.hp["ema_decay"]
        out = {}
        for name, f in factors.items():
            nf = dict(f)
            if "A" in f:
                nf["A"] = e * f["A"] + (1 - e) * a[name]
            nf["G"] = e * f["G"] + (1 - e) * g[name]
            out[name] = nf
        return out

    def invert(self, factors):
        pk = self.m.prec.kfac

        def inv(f):
            bs = f.shape[-1]
            lam = self.hp["damping"] * jnp.trace(f, axis1=-2, axis2=-1) \
                / bs + 1e-8
            eye = jnp.eye(bs, dtype=F32)
            return pk(jnp.linalg.inv(pk(f + lam[..., None, None] * eye)))

        return {name: {s + "_inv": inv(x) for s, x in f.items()}
                for name, f in factors.items()}

    def precondition(self, g, a_inv, g_inv):
        """``blockdiag(A^-1) g blockdiag(G^-1)`` for g (*stack, din, dout)."""
        stack = g.shape[:-2]
        flat = lambda x: x.reshape((-1,) + x.shape[len(stack):])
        out = _qd.KFAC.precondition(self, flat(g), flat(a_inv),
                                    flat(g_inv))
        return out.reshape(g.shape)

    def apply(self, params, grads, st):
        hp = self.hp
        gl = leaves_by_path(grads)
        pre = {}
        for name, (_, _, _, share) in self.m.lin.items():
            a_inv = st["inverses"][share or name]["A_inv"]
            pre[name] = self.precondition(gl[name], a_inv,
                                          st["inverses"][name]["G_inv"])
        dot = sum(jnp.sum(pre[k] * gl[k]) for k in sorted(pre))
        nu = jnp.minimum(1.0, hp["kl_clip"]
                         / (hp["lr"] * jnp.abs(dot) + 1e-12))
        step = st["step"] + 1
        lr, b1, b2 = hp["lr"], hp["adam_b1"], hp["adam_b2"]

        def one(path, p, g, m, mu, nv):
            k = path_of(path)
            if k in pre:
                m2 = hp["momentum"] * m + pre[k] * nu
                return p - lr * m2 - lr * hp["weight_decay"] * p, m2, mu, nv
            mu2 = b1 * mu + (1 - b1) * g
            nv2 = b2 * nv + (1 - b2) * g * g
            mhat = mu2 / (1 - b1 ** step)
            nhat = nv2 / (1 - b2 ** step)
            return p - lr * mhat / (jnp.sqrt(nhat) + hp["adam_eps"]), m, \
                mu2, nv2

        out = jax.tree_util.tree_map_with_path(
            one, params, grads, st["momentum"], st["adam_mu"],
            st["adam_nu"])
        treedef = jax.tree.structure(params)
        cols = list(zip(*treedef.flatten_up_to(out)))
        new = [jax.tree.unflatten(treedef, c) for c in cols]
        st2 = dict(st, step=step, momentum=new[1], adam_mu=new[2],
                   adam_nu=new[3])
        return new[0], st2
