"""Plain reference of a dense Qwen decoder (Qwen1.5, Qwen2) trained by the
K-FAC step that the benchmark times, written from the published
architecture and the optimizer's equations alone.

Everything is float32 ``jax.numpy`` at ``highest`` matmul precision. The
model: token embedding, ``n_layers`` pre-norm blocks (RMSNorm with a
``1 + w`` scale; grouped-query attention with q/k/v biases, rotary
position embedding over half-split channels, causal softmax; SwiGLU MLP),
a final RMSNorm and the tied embedding as the output head; the loss is
the mean next-token cross-entropy.

The K-FAC step, per step ``i`` of the trainer's cadence:

- SU, when ``i % stats_every == 0``: per factored linear ``y = x W``,
  block-diagonal Grams ``A = sum_t x x^T / T`` of its input and
  ``G = sum_t g g^T`` of ``g = dL/dy`` over the batch's ``T`` tokens,
  folded into the running factors as ``F <- ema F + (1 - ema) gram``.
  q/k/v share the A of q, gate/up share the A of gate.
- INV, when ``i % inv_every == 0``: each diagonal block ``F`` is replaced
  by ``(F + lam I)^-1``, ``lam = damping * tr(F) / bs + 1e-8``.
- FP/BP/WU every step: the gradient ``g`` of each factored weight becomes
  ``A^-1 g G^-1`` block by block, scaled by
  ``nu = min(1, kl_clip / (lr |sum(pre * g)|))``, and drives heavy-ball
  momentum ``m <- momentum m + nu pre``, ``W <- W - lr m``; every other
  parameter takes Adam.

Blocks are ``bs = block_size_for(d, cap)`` wide (one block when
``d <= cap``). Row by row and layer by layer, so it fits one chip beside
nothing else. ``prec="control"`` computes the same thing one precision
step lower: model matmul operands rounded to float8 e4m3 (scaled per
tensor) and the K-FAC Grams, inverses and preconditioning on bfloat16
operands.

This module imports nothing of the system under test.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

#: the factored linears of one block: name -> (input dim, output dim,
#: the linear whose input Gram it shares, or None)
def linears(arch: dict) -> Dict[str, Tuple[int, int, str]]:
    d, f = arch["d_model"], arch["d_ff"]
    q, kv = arch["n_heads"] * arch["head_dim"], \
        arch["n_kv_heads"] * arch["head_dim"]
    return {
        "attn/wq": (d, q, None), "attn/wk": (d, kv, "attn/wq"),
        "attn/wv": (d, kv, "attn/wq"), "attn/wo": (q, d, None),
        "mlp/wg": (d, f, None), "mlp/wu": (d, f, "mlp/wg"),
        "mlp/wd": (f, d, None),
    }


def block_size_for(d: int, cap: int, align: int = 16) -> int:
    """Width of the diagonal blocks of a ``d``-wide factor at cap ``cap``:
    one block when ``d <= cap``; else the largest width >= 128 that
    divides both ``d`` and ``d / align``; else the largest width >= 128
    that divides ``d``; else ``cap`` (the last block is zero-padded)."""
    if d <= cap:
        return d
    if d % align == 0:
        shard = d // align
        for bs in range(min(cap, shard), 127, -1):
            if shard % bs == 0 and d % bs == 0:
                return bs
    for bs in range(min(cap, d), 127, -1):
        if d % bs == 0:
            return bs
    return cap


def factor_blocks(arch: dict, cap: int) -> Dict[Tuple[str, str], Tuple]:
    """``(linear, "A"|"G") -> (n_blocks per layer, bs)`` for every factor
    the step keeps."""
    out = {}
    for name, (din, dout, share) in linears(arch).items():
        if share is None:
            bi = block_size_for(din, cap)
            out[(name, "A")] = (-(-din // bi), bi)
        bo = block_size_for(dout, cap)
        out[(name, "G")] = (-(-dout // bo), bo)
    return out


def param_count(arch: dict) -> int:
    """Weights of the matmuls (norm scales and biases left out)."""
    d, f, v = arch["d_model"], arch["d_ff"], arch["vocab"]
    per = sum(din * dout for din, dout, _ in linears(arch).values())
    return v * d + arch["n_layers"] * per


def train_flops_per_token(arch: dict, seq: int) -> float:
    """FLOPs of one forward and backward pass per token: ``6 N`` for the
    matmul weights (the tied head included) plus causal attention, whose
    query at position ``t`` scores and mixes ``t + 1`` keys: forward
    ``4 (t + 1) h hd`` per layer, averaged over ``t`` and tripled for
    the backward. Recomputation is not counted."""
    h, hd = arch["n_heads"], arch["head_dim"]
    attn = 3 * 2 * (seq + 1) * h * hd * arch["n_layers"]
    return 6.0 * param_count(arch) + attn


# -- precision -------------------------------------------------------------


def _scaled(x, exponent_bits, mantissa_bits, top):
    """Round to a narrow float with one scale per tensor: max |x| goes to
    ``top``, the format's largest normal under ``reduce_precision``."""
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits) * s


def _e4m3(x):
    return _scaled(x, 4, 3, 240.0)


def _e5m2(x):
    return _scaled(x, 5, 2, 57344.0)


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _low_mm(spec, a, b, fmt):
    """A matmul on rounded operands, float32 accumulation; its backward
    rounds the cotangent too (float8: e4m3 forward, e5m2 backward)."""
    rnd = _e4m3 if fmt == "fp8" else _bf16
    return _exact(spec, rnd(a), rnd(b))


def _low_mm_fwd(spec, a, b, fmt):
    rnd = _e4m3 if fmt == "fp8" else _bf16
    ar, br = rnd(a), rnd(b)
    return _exact(spec, ar, br), (ar, br)


def _low_mm_bwd(spec, fmt, res, ct):
    ar, br = res
    rnd = _e5m2 if fmt == "fp8" else _bf16
    _, back = jax.vjp(lambda x, y: _exact(spec, x, y), ar, br)
    return back(rnd(ct))


_low_mm.defvjp(_low_mm_fwd, _low_mm_bwd)


class Prec:
    """Precision of the model's and of K-FAC's matmuls: ``reference`` is
    float32 at ``highest``; ``control`` puts the model's matmuls on
    float8 operands and K-FAC's on bfloat16 ones."""

    def __init__(self, kind: str = "reference"):
        if kind not in ("reference", "control"):
            raise ValueError(kind)
        self.kind = kind

    def kfac(self, x):
        return x if self.kind == "reference" else _bf16(x)

    def mm(self, spec, a, b, side="model"):
        if self.kind == "reference":
            return _exact(spec, a, b)
        return _low_mm(spec, a, b, "fp8" if side == "model" else "bf16")


# -- weights ---------------------------------------------------------------


def key_for(seed: int) -> jax.Array:
    """A raw threefry key that keeps all 64 bits of ``seed``: for seeds
    under 2**32 it equals ``jax.random.PRNGKey(seed)``."""
    return jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     jnp.uint32)


def init(arch: dict, key) -> dict:
    """Weights from ``key``: embedding N(0, 0.02^2); each matmul weight
    N(0, 1/d_in); norm scales and biases zero. Keys are split as
    ``split(key, 8)``: embedding from the first, the layer stack from
    ``split(fourth, n_layers)``; per layer ``split(., 3)`` gives the
    attention (``split(., 4)``: q, k, v, o) and MLP (``split(., 3)``:
    gate, up, down) keys."""
    d, f, v = arch["d_model"], arch["d_ff"], arch["vocab"]
    q, kv = arch["n_heads"] * arch["head_dim"], \
        arch["n_kv_heads"] * arch["head_dim"]
    normal = jax.random.normal
    ks = jax.random.split(key, 8)

    def layer(k):
        la, lm, _ = jax.random.split(k, 3)
        a = jax.random.split(la, 4)
        m = jax.random.split(lm, 3)
        return {
            "ln1": jnp.zeros((d,), F32), "ln2": jnp.zeros((d,), F32),
            "attn": {
                "wq": normal(a[0], (d, q), F32) * d ** -0.5,
                "wk": normal(a[1], (d, kv), F32) * d ** -0.5,
                "wv": normal(a[2], (d, kv), F32) * d ** -0.5,
                "wo": normal(a[3], (q, d), F32) * q ** -0.5,
                "bq": jnp.zeros((q,), F32), "bk": jnp.zeros((kv,), F32),
                "bv": jnp.zeros((kv,), F32),
            },
            "mlp": {
                "wg": normal(m[0], (d, f), F32) * d ** -0.5,
                "wu": normal(m[1], (d, f), F32) * d ** -0.5,
                "wd": normal(m[2], (f, d), F32) * f ** -0.5,
            },
        }

    return {
        "embed": normal(ks[0], (v, d), F32) * 0.02,
        "final_norm": jnp.zeros((d,), F32),
        "layers": jax.vmap(layer)(jax.random.split(ks[3], arch["n_layers"])),
    }


# -- model -----------------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _pad_blocks(x, axis, bs):
    pad = (-x.shape[axis]) % bs
    if not pad:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


def _gram_sum(a, bs, prec):
    """``sum_t a_t a_t^T`` per diagonal block: (T, d) -> (nb, bs, bs)."""
    a = _pad_blocks(a, -1, bs)
    a = a.reshape(a.shape[0], -1, bs)
    return prec.mm("tib,tic->ibc", a, a, side="kfac")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gtap(y, z, bs, prec):
    """Identity on ``y``; the cotangent of ``z`` is the blocked Gram of
    ``dL/dy`` over tokens, so ``grad`` wrt ``z`` is the G statistic."""
    del z
    return y


def _gtap_fwd(y, z, bs, prec):
    del z
    return y, None


def _gtap_bwd(bs, prec, _, ct):
    return ct, _gram_sum(ct.reshape(-1, ct.shape[-1]), bs, prec)


_gtap.defvjp(_gtap_fwd, _gtap_bwd)


class Model:
    def __init__(self, arch: dict, cap: int, prec: Prec):
        self.arch, self.cap, self.prec = arch, cap, prec
        self.blocks = factor_blocks(arch, cap)

    def _linear(self, x, w, b, name, z, acts):
        """``x W (+ b)`` with the A Gram of ``x`` kept in ``acts`` (when
        collecting) and the G Gram hooked through ``z``."""
        if acts is not None and (name, "A") in self.blocks:
            acts[name] = _gram_sum(x, self.blocks[(name, "A")][1],
                                   self.prec)
        y = self.prec.mm("td,df->tf", x, w)
        if b is not None:
            y = y + b
        if z is not None:
            y = _gtap(y, z[name], self.blocks[(name, "G")][1], self.prec)
        return y

    def _layer(self, x, p, z, collect):
        """One block on one row ``x`` (T, d)."""
        ar = self.arch
        T = x.shape[0]
        h, kv, hd = ar["n_heads"], ar["n_kv_heads"], ar["head_dim"]
        acts = {} if collect else None
        pa, pm = p["attn"], p["mlp"]
        hin = _rms(x, p["ln1"], ar["norm_eps"])
        q = self._linear(hin, pa["wq"], pa["bq"], "attn/wq", z, acts)
        k = self._linear(hin, pa["wk"], pa["bk"], "attn/wk", z, acts)
        v = self._linear(hin, pa["wv"], pa["bv"], "attn/wv", z, acts)
        pos = jnp.arange(T, dtype=F32)
        q = _rope(q.reshape(T, h, hd), pos, ar["rope_theta"])
        k = _rope(k.reshape(T, kv, hd), pos, ar["rope_theta"])
        v = v.reshape(T, kv, hd)
        q = q.reshape(T, kv, h // kv, hd)
        s = self.prec.mm("tkgd,skd->kgts", q, k) * hd ** -0.5
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(causal, s, -1e30)
        o = self.prec.mm("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
        o = self._linear(o.reshape(T, h * hd), pa["wo"], None, "attn/wo",
                         z, acts)
        x = x + o
        hin = _rms(x, p["ln2"], ar["norm_eps"])
        g = self._linear(hin, pm["wg"], None, "mlp/wg", z, acts)
        u = self._linear(hin, pm["wu"], None, "mlp/wu", z, acts)
        act = jax.nn.silu(g) * u
        x = x + self._linear(act, pm["wd"], None, "mlp/wd", z, acts)
        return x, (acts if collect else {})

    def row_loss(self, params, tokens, zs, n_total, collect=False):
        """Summed next-token NLL of one row over ``n_total``, and the A
        Grams (summed over the row's tokens) when ``collect``."""
        x = params["embed"][tokens]

        def body(x, xs):
            p, z = xs
            return self._layer(x, p, z, collect)

        x, acts = jax.lax.scan(jax.checkpoint(body), x,
                               (params["layers"], zs))
        x = _rms(x, params["final_norm"], self.arch["norm_eps"])

        @jax.checkpoint
        def nll(x, head):
            logits = self.prec.mm("td,vd->tv", x[:-1], head)
            gold = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

        return nll(x, params["embed"]) / n_total, acts

    def zero_taps(self):
        L = self.arch["n_layers"]
        return {name: jnp.zeros((L, nb, bs, bs), F32)
                for (name, side), (nb, bs) in self.blocks.items()
                if side == "G"}

    def stats(self, params, tokens):
        """SU pass over a (B, T) batch: (A Grams, G Grams, mean loss)."""
        B, T = tokens.shape
        n_total = B * (T - 1)
        zs = self.zero_taps()
        step = jax.value_and_grad(
            lambda z, tok: self.row_loss(params, tok, z, n_total, True),
            has_aux=True)

        def row(carry, tok):
            (loss, acts), gz = step(zs, tok)
            a, g, l = carry
            return (jax.tree.map(jnp.add, a, acts),
                    jax.tree.map(jnp.add, g, gz), l + loss), None

        a0 = {name: jnp.zeros((self.arch["n_layers"], nb, bs, bs), F32)
              for (name, side), (nb, bs) in self.blocks.items()
              if side == "A"}
        (a, g, loss), _ = jax.lax.scan(row, (a0, zs, jnp.zeros((), F32)),
                                       tokens)
        a = {k: v / (B * T) for k, v in a.items()}
        return a, g, loss

    def grads(self, params, tokens):
        """(mean loss, gradient) over a (B, T) batch."""
        B, T = tokens.shape
        n_total = B * (T - 1)
        step = jax.value_and_grad(
            lambda p, tok: self.row_loss(p, tok, None, n_total)[0])

        def row(carry, tok):
            loss, g = step(params, tok)
            gs, l = carry
            return (jax.tree.map(jnp.add, gs, g), l + loss), None

        g0 = jax.tree.map(jnp.zeros_like, params)
        (g, loss), _ = jax.lax.scan(row, (g0, jnp.zeros((), F32)), tokens)
        return loss, g


# -- K-FAC -----------------------------------------------------------------


def path_of(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaves_by_path(tree) -> Dict[str, jax.Array]:
    return {path_of(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


class KFAC:
    """The trainer's K-FAC step in plain arithmetic (see module doc)."""

    def __init__(self, model: Model, hp: dict):
        self.m, self.hp = model, hp
        self.factored = {"layers/" + n for n in linears(model.arch)}

    def init_state(self, params):
        L = self.m.arch["n_layers"]
        factors, inverses = {}, {}
        for (name, side), (nb, bs) in self.m.blocks.items():
            full = "layers/" + name
            factors.setdefault(full, {})[side] = \
                jnp.zeros((L, nb, bs, bs), F32)
            inverses.setdefault(full, {})[side + "_inv"] = \
                jnp.broadcast_to(jnp.eye(bs, dtype=F32), (L, nb, bs, bs))
        def zeros():
            return jax.tree.map(jnp.zeros_like, params)

        return {"step": 0, "factors": factors, "inverses": inverses,
                "momentum": zeros(), "adam_mu": zeros(),
                "adam_nu": zeros()}

    def update_factors(self, factors, a, g):
        e = self.hp["ema_decay"]
        out = {}
        for full, f in factors.items():
            name = full.split("/", 1)[1]
            nf = dict(f)
            if "A" in f:
                nf["A"] = e * f["A"] + (1 - e) * a[name]
            nf["G"] = e * f["G"] + (1 - e) * g[name]
            out[full] = nf
        return out

    def invert(self, factors):
        pk = self.m.prec.kfac

        def inv(f):
            bs = f.shape[-1]
            lam = self.hp["damping"] * jnp.trace(f, axis1=-2, axis2=-1) \
                / bs + 1e-8
            eye = jnp.eye(bs, dtype=F32)
            return pk(jnp.linalg.inv(pk(f + lam[..., None, None] * eye)))

        return {full: {s + "_inv": inv(x) for s, x in f.items()}
                for full, f in factors.items()}

    def precondition(self, g, a_inv, g_inv):
        """``blockdiag(A^-1) g blockdiag(G^-1)`` for g (L, d_in, d_out)."""
        L, din, dout = g.shape
        bi, bo = a_inv.shape[-1], g_inv.shape[-1]
        gp = _pad_blocks(_pad_blocks(g, 1, bi), 2, bo)
        nbi, nbo = gp.shape[1] // bi, gp.shape[2] // bo
        gp = gp.reshape(L, nbi, bi, nbo, bo)
        mm = self.m.prec.mm
        t = mm("liab,libjc->liajc", a_inv, gp, side="kfac")
        out = mm("liajc,ljcd->liajd", t, g_inv, side="kfac")
        return out.reshape(L, nbi * bi, nbo * bo)[:, :din, :dout]

    def apply(self, params, grads, st):
        hp = self.hp
        lin = linears(self.m.arch)
        gl = leaves_by_path(grads)
        pre = {}
        for name, (_, _, share) in lin.items():
            full = "layers/" + name
            a_inv = st["inverses"]["layers/" + (share or name)]["A_inv"]
            pre[full] = self.precondition(gl[full], a_inv,
                                          st["inverses"][full]["G_inv"])
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
