"""Fixed-point quantization and bit-slicing utilities.

These model the digital view of the ReRAM datapath in RePAST:

- DAC inputs are ``R_DAC``-bit slices of a ``Q_b``-bit fixed-point vector
  (paper Eqn. 6, "Loop b").
- ADC outputs deliver ``R_ADC`` bits of the analog result per conversion
  ("Loop x").
- A ReRAM cell stores ``R_c`` bits; ``k`` chained crossbars hold the top
  ``k * R_c`` bits of the matrix (``A_H``); the remainder is ``A_L``
  (paper Sec. III-A.3).

Everything is implemented with jnp so it is jit-able and differentiable
where it needs to be (straight-through estimators are NOT needed here:
quantization only appears in the preconditioner path, never in the loss).

Conventions: a value ``v`` with ``bits`` fractional bits on scale ``s``
is represented as ``v ≈ s * round(v / s * 2**bits) * 2**-bits``. All
quantizers are symmetric and saturating.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Tuple

import jax
import jax.numpy as jnp


def amax_scale(x: jax.Array, axis=None) -> jax.Array:
    """Symmetric max-abs scale (never zero)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return jnp.where(s == 0, jnp.ones_like(s), s)


def quantize_fixed(x: jax.Array, bits: int, scale: jax.Array) -> jax.Array:
    """Quantize ``x`` onto a ``bits``-fractional-bit grid of ``scale``.

    Returns the *dequantized* value (i.e. a float on the grid). Values are
    clipped to (-scale, scale).
    """
    step = scale * (2.0 ** (-bits))
    return quantize_int(x, bits, scale) * step


def quantize_int(x: jax.Array, bits: int, scale: jax.Array) -> jax.Array:
    """Quantize to signed integer grid codes in [-(2**bits - 1), 2**bits - 1].

    The clip is symmetric: the two's-complement endpoint ``-2**bits``
    would need ``bits + 1`` magnitude bits, which the sign/magnitude
    slice decomposition (:func:`bit_slices_fixed`, ``ceil(bits/slice)``
    slices) cannot carry — it would silently drop the top bit and
    reconstruct 0 for exactly the saturated-negative input.
    """
    step = scale * (2.0 ** (-bits))
    q = jnp.round(x / step)
    return jnp.clip(q, -(2.0 ** bits - 1), 2.0 ** bits - 1)


def split_hi_lo_fixed(
    x: jax.Array, total_bits: int, hi_bits: int, scale: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Split a ``total_bits`` fixed-point value into hi/lo parts.

    ``x_q = x_hi + x_lo * 2**-hi_bits`` where
      - ``x_hi`` is ``x`` truncated to its top ``hi_bits`` fractional bits,
      - ``x_lo = (x_q - x_hi) * 2**hi_bits`` holds the remaining
        ``total_bits - hi_bits`` bits, pre-shifted so its magnitude is
        comparable to ``scale`` (paper: ``A_L = (A - A_H) * 2**(k*R_c)``).

    Mirrors the paper's matrix split: ``A_H`` programmed into INV
    crossbars, ``A_L`` into a VMM crossbar.
    """
    xq = quantize_fixed(x, total_bits, scale)
    step_hi = scale * (2.0 ** (-hi_bits))
    hi = jnp.floor(xq / step_hi) * step_hi
    lo = (xq - hi) * (2.0 ** hi_bits)
    return hi, lo


def bit_slices_fixed(
    x: jax.Array, total_bits: int, slice_bits: int, scale: jax.Array
) -> list[jax.Array]:
    """Decompose a quantized value into ``ceil(total/slice)`` unsigned-ish
    slices, LSB-first, such that ``sum_i slices[i] * 2**(i*slice_bits - total_bits) * scale``
    reconstructs the value.  Used by "Loop b" (DAC slicing).

    Each returned slice is a float holding an integer in
    ``[0, 2**slice_bits)`` (plus a sign carried on the leading slice),
    exactly what an ``R_DAC``-bit DAC can emit after the driver handles
    two's-complement.
    """
    n = -(-total_bits // slice_bits)
    q = quantize_int(x, total_bits, scale)  # codes in [-(2**T - 1), 2**T - 1]
    # Work with a sign/magnitude representation: the analog driver applies
    # the sign by swapping the differential pair; each slice is unsigned.
    sign = jnp.sign(q)
    mag = jnp.abs(q)
    out = []
    for _ in range(n):
        out.append(sign * jnp.mod(mag, 2.0 ** slice_bits))
        mag = jnp.floor(mag / (2.0 ** slice_bits))
    return out


def reconstruct_slices(
    slices: list[jax.Array], total_bits: int, slice_bits: int, scale: jax.Array
) -> jax.Array:
    """Inverse of :func:`bit_slices_fixed` (the digital S+A unit)."""
    acc = jnp.zeros_like(slices[0])
    for i, s in enumerate(slices):
        acc = acc + s * (2.0 ** (i * slice_bits))
    return acc * scale * (2.0 ** (-total_bits))


# ---------------------------------------------------------------------------
# TPU production path: hi/lo decomposition in bf16 ("bit-slicing" for the MXU)
# ---------------------------------------------------------------------------

def round_bf16(x: jax.Array) -> jax.Array:
    """fp32 ``x`` rounded to bf16 (nearest-even), kept in fp32.

    Rounded on the bits and never as a bf16 round trip: XLA may fold an
    ``f32 -> bf16 -> f32`` convert pair to the identity (excess precision
    is allowed by default), and on a TPU it does, so ``x - hi`` and every
    lo slice built from it become zero. Integer ops lower both in XLA and
    in Mosaic, which has no ``reduce_precision``, so the Pallas kernels
    split with this same function. Bitwise the nearest-even conversion
    for every non-NaN input (a NaN may come out as inf; its lo slice
    stays NaN).
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    lsb = jax.lax.shift_right_logical(bits, 16) & 1
    bits = (bits + (0x7FFF + lsb)) & ~0xFFFF
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_hi_lo_bf16(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Split an fp32 array into two bf16 arrays such that
    ``hi + lo ≈ x`` with ~16 mantissa bits of effective precision.

    This is the MXU analogue of programming ``A_H`` into INV crossbars and
    ``A_L`` into VMM crossbars: each half is representable by the
    low-precision compute primitive (bf16), their composition recovers
    (near-)fp32 precision.
    """
    x = x.astype(jnp.float32)
    hi = round_bf16(x)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _dot(x, y, lhs_contract, precision=None):
    """``x`` contracted on ``lhs_contract`` with ``y``'s first dim,
    accumulated in fp32."""
    return jax.lax.dot_general(
        x, y, (((lhs_contract,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


def hilo_matmul(a: jax.Array, b: jax.Array, *, precision=None) -> jax.Array:
    """fp32-accurate matmul where every MXU operand is bf16.

    ``a @ b = (a_hi + a_lo) @ (b_hi + b_lo)`` expanded into three partial
    products (the ``a_lo @ b_lo`` term is below the fp32 noise floor and
    dropped — same argument as the paper's Eqn. 13 dropping
    ``A_1L·A_2L``), each accumulated in fp32.
    """
    a_hi, a_lo = split_hi_lo_bf16(a)
    b_hi, b_lo = split_hi_lo_bf16(b)
    k = a.ndim - 1
    return (_dot(a_hi, b_hi, k, precision) + _dot(a_hi, b_lo, k, precision)
            + _dot(a_lo, b_hi, k, precision))


def hilo_matmul_tn(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a.T @ b`` for 2-D operands as the three partial products of
    :func:`hilo_matmul`, contracting the leading dims (a Gram
    accumulation without a transpose)."""
    a_hi, a_lo = split_hi_lo_bf16(a)
    b_hi, b_lo = split_hi_lo_bf16(b)
    return _dot(a_hi, b_hi, 0) + _dot(a_hi, b_lo, 0) + _dot(a_lo, b_hi, 0)


def hilo_matmul_exact_lhs(a16: jax.Array, b: jax.Array, *,
                          precision=None) -> jax.Array:
    """``a16 @ b`` where ``a16`` is *exactly representable* in bf16
    (e.g. the A_H slice, which is bf16-rounded by construction): its lo
    slice is identically zero, so only two partial products are needed
    (EXPERIMENTS.md §Perf 3.1 — a 1/3 MXU-flop saving on every matmul
    against a hi-slice operand)."""
    b_hi, b_lo = split_hi_lo_bf16(b)
    a16 = a16.astype(jnp.bfloat16)
    k = a16.ndim - 1
    return _dot(a16, b_hi, k, precision) + _dot(a16, b_lo, k, precision)


# ---------------------------------------------------------------------------
# Low-precision einsum: one routing point for the WU graph's matmuls
# ---------------------------------------------------------------------------

#: The shipping knob values (``--precision`` on repro.launch.train).
PRECISIONS = ("fp32", "hilo", "int8")

# extended spellings for the precision ladder: "int<total>b<slice>" is an
# integer-sliced product with <total>-bit codes composed from <slice>-bit
# slices (e.g. "int16b4": 4 chained 4-bit DAC slices per operand)
_INT_SPEC = re.compile(r"^int(\d+)b(\d+)$")


def precision_kind(precision):
    """Parse a precision spec into ``'fp32' | 'hilo' | (total, slice)``.

    ``"int8"`` — the shipping int8 mode — means 8-bit *hardware operands*:
    24-bit fixed-point codes composed from three 8-bit slices per side,
    the ISAAC-style exact bit-sliced VMM. ``"int<T>b<S>"`` spells any
    other rung of the ladder explicitly.
    """
    if precision in (None, "fp32"):
        return "fp32"
    if precision == "hilo":
        return "hilo"
    if precision == "int8":
        return (24, 8)
    m = _INT_SPEC.match(str(precision))
    if m:
        total, sl = int(m.group(1)), int(m.group(2))
        if not (1 <= sl <= total):
            raise ValueError(
                f"precision {precision!r}: need 1 <= slice bits "
                f"<= total bits, got total={total} slice={sl}")
        return (total, sl)
    raise ValueError(
        f"unknown precision {precision!r}; expected one of "
        f"{PRECISIONS} or 'int<total>b<slice>' (e.g. 'int16b4')")


def split_limbs_bf16(x: jax.Array, limbs: int = 3) -> list[jax.Array]:
    """Generalized :func:`split_hi_lo_bf16`: ``sum(limbs) ≈ x`` with
    each limb bf16 and limb ``i`` carrying mantissa bits ``[8i, 8i+8)``
    — the MXU image of chaining ``k`` ReRAM cell columns per value."""
    r = x.astype(jnp.float32)
    out = []
    for _ in range(limbs):
        l = round_bf16(r)
        out.append(l.astype(jnp.bfloat16))
        r = r - l
    return out


def hilo_einsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``einsum(spec, a, b)`` where every contraction operand is bf16.

    Unlike :func:`hilo_matmul` (two limbs, three partials — enough
    inside self-correcting Newton-Schulz loops), the WU einsums are
    one-shot, and the budget is >= 16 effective bits on the update
    *after two chained products*. A 2-limb split leaves ~2**-18
    operand error -> ~15.4 achieved bits on the smoke-arch update
    (measured), just under budget. Three limbs per operand and the six
    partials of combined limb order <= 2 put the operand error at
    ~2**-27; the dropped (mid*lo, lo*lo) terms are below 2**-36.
    :func:`kernels.bitslice_mm` is the Pallas TPU form of the same
    partial-product scheme.
    """
    a_l = split_limbs_bf16(a, 3)
    b_l = split_limbs_bf16(b, 3)

    def ein(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    acc = None
    for i in range(3):
        for j in range(3):
            if i + j > 2:
                continue
            p = ein(a_l[i], b_l[j])
            acc = p if acc is None else acc + p
    return acc


def int_slice_einsum(spec: str, a: jax.Array, b: jax.Array, *,
                     total_bits: int = 24,
                     slice_bits: int = 8) -> jax.Array:
    """Exact bit-sliced ``einsum(spec, a, b)`` of the quantized operands.

    Each operand is quantized to ``total_bits``-bit fixed-point codes on
    its per-tensor amax scale and decomposed into ``ceil(total/slice)``
    sign/magnitude slices; every pairwise slice product runs as its own
    einsum (the crossbar pass) and is shift-added with weight
    ``2**((i+j)*slice)`` (the digital S+A unit). The composition is
    *exact* in the quantized codes, so the only error is the operand
    quantization itself (~2**-total relative) — "more slices composed,
    more accurate", the paper's Loop-b story applied to the WU graph.
    """
    sa = amax_scale(a)
    sb = amax_scale(b)
    a_sl = bit_slices_fixed(a, total_bits, slice_bits, sa)
    b_sl = bit_slices_fixed(b, total_bits, slice_bits, sb)
    acc = None
    for i, asl in enumerate(a_sl):
        for j, bsl in enumerate(b_sl):
            part = jnp.einsum(spec, asl, bsl,
                              preferred_element_type=jnp.float32)
            part = part * (2.0 ** ((i + j) * slice_bits))
            acc = part if acc is None else acc + part
    return acc * (sa * sb) * (2.0 ** (-2 * total_bits))


def lowp_einsum(spec: str, a: jax.Array, b: jax.Array, *,
                precision: str = "fp32") -> jax.Array:
    """The WU graph's single matmul routing point.

    ``precision="fp32"`` is ``jnp.einsum(spec, a, b,
    preferred_element_type=jnp.float32)`` pinned to ``HIGHEST``: a TPU
    runs an unpinned fp32 einsum as one bf16 pass (about 8 bits), the
    CPU ignores the pin (bitwise the historical einsum). ``"hilo"``
    routes through bf16 limb products, ``"int8"`` / ``"int<T>b<S>"``
    through the sliced integer product.
    """
    kind = precision_kind(precision)
    if kind == "fp32":
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
    if kind == "hilo":
        return hilo_einsum(spec, a, b)
    total, sl = kind
    return int_slice_einsum(spec, a, b, total_bits=total, slice_bits=sl)


@dataclasses.dataclass(frozen=True)
class CircuitConfig:
    """Parameters of the modeled RePAST datapath (paper Sec. III/VI-A)."""

    q_a: int = 16       # bits of the SOI matrix A
    q_b: int = 16       # bits of the rhs vector b
    q_x: int = 16       # bits of the solution x
    r_dac: int = 4      # DAC resolution (paper: 4-bit)
    r_adc: int = 8      # ADC resolution (paper: 8-bit)
    r_c: int = 4        # bits per ReRAM cell (paper: 4-bit)
    k: int = 2          # chained INV crossbars -> A_H has k*r_c bits
    n_taylor: int = 18  # Loop A iterations (paper Fig. 4(b): 18)

    @property
    def hi_bits(self) -> int:
        return self.k * self.r_c

    @property
    def loops_x(self) -> int:
        return -(-self.q_x // self.r_adc)

    @property
    def loops_b(self) -> int:
        return -(-self.q_b // self.r_dac)

    def cycles_inv(self) -> int:
        """Paper Eqn. 10: cycles of one high-precision INV."""
        return self.n_taylor * (
            2 * self.loops_b * self.loops_x + -(-self.q_x // self.r_dac))

    def cycles_inv_fused(self) -> int:
        """Paper Eqn. 14: cycles of one fused MM+INV high-precision INV."""
        return self.n_taylor * (
            2 * self.loops_b * self.loops_x + 2 * -(-self.q_x // self.r_dac))
