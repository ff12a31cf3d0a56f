"""High-precision matrix inversion composed from low-precision primitives.

This is the paper's central contribution (RePAST Sec. III). Two
implementations live here:

1. ``faithful_inv_apply`` — a numerically faithful behavioral model of the
   ReRAM circuit (NumPy, float64 carrier): the INV crossbar stores only the
   top ``k*R_c`` bits of ``A`` (``A_H``), DACs deliver ``R_DAC``-bit input
   slices, ADCs emit ``R_ADC`` bits per conversion, and the three nested
   loops of Fig. 4(a) — Loop b (DAC slicing, Eqn. 6), Loop x (ADC residual
   refinement) and Loop A (Taylor/Neumann series over the ``A_H/A_L``
   split, Eqn. 8/9) — compose a >=16-bit accurate solve. This is the
   direct analogue of the paper's Verilog behavioural verification and is
   what reproduces Fig. 4(b).

2. ``composed_inverse`` / ``mxu_inv_apply`` — the TPU production path
   (JAX): the "low-precision primitive" is the bf16 MXU matmul; ``A`` is
   split into bf16 hi/lo slices exactly like ``A_H``/``A_L``; a
   Newton–Schulz iteration plays the role of the analog INV crossbar
   (cheap, low-precision inverse of ``A_H``); the same Neumann series +
   iterative refinement recovers fp32-accurate inverses while every
   matrix-matrix operand the MXU sees is bf16. This is used by the K-FAC
   optimizer for SOI block inversion (see ``core/kfac.py``) and is backed
   by the Pallas kernel in ``kernels/neumann_inv``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.quantize import (
    CircuitConfig,
    hilo_matmul,
    hilo_matmul_exact_lhs,
    split_hi_lo_bf16,
)

__all__ = [
    "CircuitConfig",
    "faithful_inv_apply",
    "faithful_fused_gram_inv_apply",
    "newton_schulz_inverse",
    "composed_inverse",
    "mxu_inv_apply",
    "achieved_bits",
]


# ---------------------------------------------------------------------------
# Behavioral circuit model (NumPy / float64 carrier)
# ---------------------------------------------------------------------------

def _quant(x: np.ndarray, bits: int, scale: float) -> np.ndarray:
    # symmetric clip: the sign/magnitude converters have no -2**bits code
    # (same saturation contract as core.quantize.quantize_int)
    step = scale * 2.0 ** (-bits)
    q = np.round(x / step)
    np.clip(q, -(2.0 ** bits - 1), 2.0 ** bits - 1, out=q)
    return q * step


def _pow2_range(x: np.ndarray) -> float:
    """Auto-ranging converter scale: smallest power of two >= max|x|.

    Models the programmable-gain stage in front of the ADC (the paper's
    shift alignment between loop iterations keeps signals in range)."""
    m = float(np.max(np.abs(x)))
    if m == 0.0 or not np.isfinite(m):
        return 1.0
    return float(2.0 ** np.ceil(np.log2(m)))


def _adc(x: np.ndarray, cfg: CircuitConfig) -> np.ndarray:
    """R_ADC-bit conversion at an auto-ranged power-of-two scale."""
    return _quant(x, cfg.r_adc, _pow2_range(x))


def _split_hi_lo(A: np.ndarray, total_bits: int, hi_bits: int, scale: float):
    """Round-to-nearest hi/lo split. Rounding (not truncation) keeps the
    residue ``A_L`` zero-mean and signed, which is what makes the Neumann
    series contract (||A - A_H|| ~ sqrt(n) 2^-hi instead of n 2^-hi).
    Signed cell values are realized with differential crossbar pairs,
    standard in ReRAM designs."""
    Aq = _quant(A, total_bits, scale)
    step_hi = scale * 2.0 ** (-hi_bits)
    hi = np.round(Aq / step_hi) * step_hi
    lo = (Aq - hi) * 2.0 ** hi_bits
    return hi, lo


def _analog_inv_crossbar(A_H_lu, b: np.ndarray, cfg: CircuitConfig) -> np.ndarray:
    """One pass through the INV crossbar array.

    The analog OpAmp feedback settles to the exact solution of
    ``A_H x = b`` (paper Eqn. 4/5, O(1) settle); the only loss is the
    output conversion: R_ADC bits at an auto-ranged scale.
    """
    import scipy.linalg as sla

    x = sla.lu_solve(A_H_lu, b)
    return _adc(x, cfg)


def _hp_vmm(M: np.ndarray, v: np.ndarray, cfg: CircuitConfig) -> np.ndarray:
    """High-precision bit-sliced VMM (ISAAC-style, paper Sec. II-B).

    Unlike INV, VMM distributes over bit slices: with both operands
    already on fixed-point grids, per-slice partial products are small
    integers, the digital S+A accumulators are wide, and the composed
    product is *exact* (this is the standard ISAAC precision argument;
    the paper relies on it for the A_L / residual VMMs). The precision
    limiters in this model are therefore the operand grids themselves
    (Q_A-bit matrices, ADC/DAC-quantized vectors), not the VMM."""
    return M @ v


def _loop_b_solve(A_H_lu, r: np.ndarray, cfg: CircuitConfig,
                  rhs_scale: float) -> np.ndarray:
    """Loop b (Eqn. 6): slice the rhs into R_DAC-bit DAC inputs, solve each
    slice on the INV crossbar, shift-and-add the ADC outputs."""
    step = rhs_scale * 2.0 ** (-cfg.q_b)
    q = np.round(r / step)
    # symmetric clip: code -2**q_b would need q_b + 1 magnitude bits and
    # the loops_b slices below would silently drop its top bit, turning a
    # DAC-grid-saturating rhs component into 0 (and Loop x can never
    # recover it: the residual re-saturates at every rescale)
    np.clip(q, -(2.0 ** cfg.q_b - 1), 2.0 ** cfg.q_b - 1, out=q)
    sign = np.sign(q)
    mag = np.abs(q)
    acc = np.zeros_like(r)
    for i in range(cfg.loops_b):
        sl = sign * np.mod(mag, 2.0 ** cfg.r_dac)          # R_DAC-bit slice
        mag = np.floor(mag / 2.0 ** cfg.r_dac)
        # slice is worth  sl * 2**(i*r_dac) * step  in real units
        sl_val = sl * (2.0 ** (i * cfg.r_dac)) * step
        acc = acc + _analog_inv_crossbar(A_H_lu, sl_val, cfg)
    return acc


def _loop_x_solve(A_H_lu, vmm_a, b: np.ndarray, cfg: CircuitConfig,
                  scale: float) -> np.ndarray:
    """Loop x: iterative residual refinement around the ADC.

    Each round quantizes ~R_ADC more bits of x:
      ``x_j = ADC(A_H^{-1} b_j)``;  ``b_{j+1} = (b_j - A x_j) * 2^{R_ADC}``.
    Per the paper (Sec. III-A.2), "the matrix A participates in a VMM
    computation ... carried out by the INV crossbars storing A": the
    residual uses the *full* matrix (``A_H`` on the INV crossbars plus
    ``A_L`` on its VMM crossbar, both bit-sliced high-precision VMMs), so
    the refinement contracts toward the true solution rather than the
    truncated one. ``vmm_a`` implements that product.

    Error analysis: the analog solve is exact, so round ``j``'s output
    error is its ADC truncation; the residual rescale by ``2^{R_ADC}``
    re-centers it in converter range and the next round recovers it. The
    ``A_L`` part of the residual additionally contracts the Taylor error
    by ``rho(A_H^{-1} A_L 2^{-hi})`` per round.
    """
    x_acc = np.zeros_like(b)
    r = b
    for j in range(cfg.loops_x):
        xj = _loop_b_solve(A_H_lu, r, cfg, rhs_scale=_pow2_range(r))
        x_acc = x_acc + xj * 2.0 ** (-j * cfg.r_adc)
        r = (r - vmm_a(xj)) * 2.0 ** cfg.r_adc
    return x_acc


def quantize_problem(
    A: np.ndarray, b: np.ndarray, cfg: CircuitConfig = CircuitConfig()
) -> Tuple[np.ndarray, np.ndarray]:
    """The Q_A/Q_b-bit view of the problem the circuit actually solves.

    The paper's accuracy yardstick ("matrix, input vector and result are
    all 16-bit quantized", Fig. 4(b)) is the exact solution of *this*
    problem; quantization of the problem itself is the separate,
    algorithm-level study of Fig. 3.
    """
    A = np.asarray(A, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    s_A = float(np.max(np.abs(A))) or 1.0
    A_H, A_L = _split_hi_lo(A, cfg.q_a, cfg.hi_bits, s_A)
    Aq = A_H + A_L * 2.0 ** (-cfg.hi_bits)
    bq = _quant(b, cfg.q_b, _pow2_range(b))
    return Aq, bq


def faithful_inv_apply(
    A: np.ndarray,
    b: np.ndarray,
    cfg: CircuitConfig = CircuitConfig(),
    return_trace: bool = False,
) -> np.ndarray | Tuple[np.ndarray, list]:
    """Solve ``x = A^{-1} b`` with the full three-loop RePAST scheme.

    ``A``: (n, n) symmetric (Tikhonov-damped SOI block).
    ``b``: (n,) or (n, m) rhs.

    Converges iff the Neumann series contracts: ``rho(A_H^{-1}(A - A_H)) < 1``
    — the paper's small-condition-number requirement, guaranteed in
    second-order training by Tikhonov damping (Sec. III-A.3).

    If ``return_trace``, also returns the partial solution after each
    Loop-A iteration (used to reproduce Fig. 4(b)).
    """
    import scipy.linalg as sla

    A = np.asarray(A, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    s_A = float(np.max(np.abs(A))) or 1.0
    A_H, A_L = _split_hi_lo(A, cfg.q_a, cfg.hi_bits, s_A)
    b = _quant(b, cfg.q_b, _pow2_range(b))
    A_H_lu = sla.lu_factor(A_H)

    def vmm_a(x):
        # full-matrix VMM: A_H (INV crossbars, VMM-wired) + A_L (VMM xbar)
        return _hp_vmm(A_H, x, cfg) + _hp_vmm(A_L, x, cfg) * 2.0 ** (-cfg.hi_bits)

    # Loop A. We implement the Taylor series in its error-feedback form:
    #   x   <- x + LoopX(A_H^{-1}, r)
    #   r   <- r - A x_l            (one more VMM: A_L slice + A_H slice)
    # Expanding the recurrence reproduces exactly the alternating series
    # A_H^{-1}(I - P + P^2 - ...) b of Eqn. 9 — Fig. 5(c)'s signed S+A is
    # the unrolled view of the same dataflow — while keeping every
    # intermediate in converter range (the paper's shift alignment).
    # Cycle count per iteration is unchanged: one Loop-x chain + one VMM.
    def out_reg(x):
        # The accumulated result lives in a Q_x-bit output register
        # (paper: "result x is 16-bit quantized").
        return _quant(x, cfg.q_x, _pow2_range(x))

    x_acc = np.zeros_like(b)
    r = b
    trace = []
    for _ in range(cfg.n_taylor):
        x_l = _loop_x_solve(A_H_lu, vmm_a, r, cfg, scale=_pow2_range(r))
        x_acc = x_acc + x_l
        if return_trace:
            trace.append(out_reg(x_acc))
        r = r - vmm_a(x_l)
    x_acc = out_reg(x_acc)
    if return_trace:
        return x_acc, trace
    return x_acc


def faithful_fused_gram_inv_apply(
    a: np.ndarray,
    b: np.ndarray,
    damping: float,
    cfg: CircuitConfig = CircuitConfig(),
) -> np.ndarray:
    """Fused MM+INV (paper Sec. IV-B, Eqn. 11-13): solve
    ``x = (a a^T + damping I)^{-1} b`` without ever materializing the Gram
    at full precision. ``a``: (n, m). The hi/lo split is applied to the
    *factors*: ``A_H = a_H a_H^T + damping I`` lives on the fused INV
    crossbars, ``A_L = a_H a_L^T + a_L (a_H + a_L)^T`` on VMM crossbars
    (exactly Eqn. 13 with both cross terms kept).
    """
    import scipy.linalg as sla

    a = np.asarray(a, dtype=np.float64)
    s_a = float(np.max(np.abs(a))) or 1.0
    a_H, a_L = _split_hi_lo(a, cfg.q_a, cfg.hi_bits, s_a)
    a_L = a_L * 2.0 ** (-cfg.hi_bits)  # back to real units for the model
    A_H = a_H @ a_H.T + damping * np.eye(a.shape[0])
    A_H_lu = sla.lu_factor(A_H)

    aq = a_H + a_L  # the Q_A-bit view of a (a_L already in real units here)

    def vmm_a(x):
        # Full Gram VMM without materializing it: A x = a (a^T x) + damp x,
        # realized as two chained bit-sliced VMMs (the paper's Eqn. 13
        # split runs the hi/lo pieces on different crossbars in parallel;
        # numerically the sum is the same product).
        return _hp_vmm(aq, _hp_vmm(aq.T, x, cfg), cfg) + damping * x

    x_acc = np.zeros_like(b, dtype=np.float64)
    r = np.asarray(b, dtype=np.float64)
    for _ in range(cfg.n_taylor):
        x_l = _loop_x_solve(A_H_lu, vmm_a, r, cfg, scale=_pow2_range(r))
        x_acc = x_acc + x_l
        r = r - vmm_a(x_l)
    return x_acc


def achieved_bits(x: np.ndarray, x_ref: np.ndarray) -> float:
    """Relative accuracy of ``x`` vs ``x_ref`` in bits: -log2(relerr)."""
    num = float(np.max(np.abs(x - x_ref)))
    den = float(np.max(np.abs(x_ref))) or 1.0
    if num == 0:
        return 64.0
    return float(-np.log2(num / den))


# ---------------------------------------------------------------------------
# TPU production path (JAX; bf16 MXU primitives)
# ---------------------------------------------------------------------------

def _norm_bound(A: jax.Array) -> jax.Array:
    """Cheap upper bound on ||A||_2: sqrt(||A||_1 * ||A||_inf)."""
    n1 = jnp.max(jnp.sum(jnp.abs(A), axis=-2))
    ninf = jnp.max(jnp.sum(jnp.abs(A), axis=-1))
    return jnp.sqrt(n1 * ninf)


def newton_schulz_inverse(
    A: jax.Array,
    n_iters: int = 18,
    *,
    hilo: bool = True,
    exact_bf16: bool = False,
) -> jax.Array:
    """Explicit inverse via Newton–Schulz: ``X <- X (2I - A X)``.

    With ``hilo=True`` every matmul runs as bf16 hi/lo partial products
    (MXU-only datapath) — the TPU stand-in for the analog INV crossbar.
    ``exact_bf16`` marks ``A`` as exactly bf16-representable (the A_H
    slice): its product then needs only two partials (§Perf 3.1).
    Converges quadratically for SPD ``A`` once ``X0 = A / ||A||^2``.
    """
    A = A.astype(jnp.float32)
    n = A.shape[-1]
    eye = jnp.eye(n, dtype=jnp.float32)
    x0 = A / (_norm_bound(A) ** 2)

    mm = hilo_matmul if hilo else (
        lambda a, b: jax.lax.dot_general(
            a, b, (((a.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    mm_a = (hilo_matmul_exact_lhs if (hilo and exact_bf16) else mm)
    a16 = A.astype(jnp.bfloat16) if (hilo and exact_bf16) else A

    def body(x, _):
        ax = mm_a(a16, x)
        x = mm(x, 2.0 * eye - ax)
        return x, None

    x, _ = jax.lax.scan(body, x0, None, length=n_iters)
    return x


def composed_inverse(
    A: jax.Array,
    damping: float | jax.Array = 0.0,
    *,
    ns_iters: int = 18,
    taylor_terms: int = 4,
    refine_steps: int = 1,
) -> jax.Array:
    """The paper's composed-precision inverse, MXU dialect.

    1. Split ``A + damping I = A_H + A_L`` (bf16 hi/lo == k*R_c-bit split).
    2. ``Y ~= A_H^{-1}``: Newton–Schulz on the *hi* slice with bf16
       matmuls — the low-precision INV primitive.
    3. Loop A (Neumann, Eqn. 9): ``M = sum_l (-Y A_L)^l Y``.
    4. Loop x (iterative refinement on the inverse): ``M <- M + M(I - A M)``
       recovering the bits the low-precision primitive lost.

    Returns an fp32 inverse while all O(n^3) work is bf16. Its accuracy
    is bounded by the 16 significand bits of the bf16 hi/lo operands:
    on 64 damped 128x128 blocks at relative damping 0.03 it reads 15.09
    bits of max-relative error against a float64 inverse, on a TPU v5e
    and on the CPU alike (``chip_smoke.py``'s precision phase).
    """
    A = A.astype(jnp.float32)
    n = A.shape[-1]
    eye = jnp.eye(n, dtype=jnp.float32)
    Ad = A + damping * eye
    A_hi16, A_lo16 = split_hi_lo_bf16(Ad)
    A_hi = A_hi16.astype(jnp.float32)

    y = newton_schulz_inverse(A_hi, ns_iters, hilo=True,
                              exact_bf16=True)

    # Loop A: Neumann series over the lo slice (A_lo exactly bf16 =>
    # two-partial products, §Perf 3.1).
    def taylor_body(carry, _):
        m, t = carry
        t = -hilo_matmul(y, hilo_matmul_exact_lhs(A_lo16, t))
        return (m + t, t), None

    (m, _), _ = jax.lax.scan(taylor_body, (y, y), None,
                             length=max(taylor_terms - 1, 0))

    # Loop x analogue: refinement against the full-precision A.
    def refine_body(m, _):
        r = eye - hilo_matmul(Ad, m)
        return m + hilo_matmul(m, r), None

    m, _ = jax.lax.scan(refine_body, m, None, length=refine_steps)
    return m


def mxu_inv_apply(
    A: jax.Array,
    B: jax.Array,
    damping: float | jax.Array = 0.0,
    **kw,
) -> jax.Array:
    """Solve ``(A + damping I)^{-1} B`` on the composed-precision path."""
    M = composed_inverse(A, damping, **kw)
    return hilo_matmul(M, B)
