"""On-chip smoke test: the K-FAC trainer, the serving engine and the SOI
inversion precision at qwen2-0.5b full width, on a TPU.

    python chip_smoke.py             # one chip: train, serve, precision
    python chip_smoke.py --chips 4   # four chips: the sharded trainer
                                     # (2x2 --dist-inv, --pp 2) against
                                     # the same steps on one chip
    python chip_smoke.py --chips 4 --plant-fault
                                     # the same, plus a 2x2 run whose
                                     # owner exchange is skipped: it
                                     # must miss the tolerance
    python chip_smoke.py --lr 3e-2   # train at another step size (the
                                     # trainer's default overshoots)

The parent never imports JAX: each phase runs in a child process of its
own, which holds the chip, prints one JSON line last and exits. The run's
last line is ``{"ok": true, "device": {...}}``. A failed phase, or a
backend other than TPU, exits nonzero without that line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Step size of every training run. The trainer's default, 3e-2,
#: overshoots at qwen2-0.5b full width: one chip's losses at batch
#: 8 x 1024 go 12.09, 12.79, 17.65, 14.78, 14.95, 16.26. At 3e-3 the
#: loss descends, which the train phase checks.
LR = "3e-3"
TRAIN_ARGV = ["--arch", "qwen2-0.5b", "--batch", "8", "--seq", "1024",
              "--steps", "6", "--stats-every", "2", "--inv-every", "2"]
SERVE_ARGV = ["--arch", "qwen2-0.5b", "--requests", "8", "--max-slots",
              "4", "--prompt-len", "128", "--gen", "32", "--greedy"]
#: The four-chip comparison: same widths, a shorter sequence (the
#: layouts under test do not depend on it; compile time dominates).
MULTI_ARGV = ["--arch", "qwen2-0.5b", "--batch", "8", "--seq", "256",
              "--steps", "4", "--stats-every", "2", "--inv-every", "2"]
#: Per-step loss agreement between layouts, relative. Reordered bf16
#: sums move the loss by far less than one bf16 rounding (2^-8): the
#: worst difference measured on v5e is 2.87e-4, so 2^-10 leaves 3.4x
#: of room, while a skipped owner exchange misses it (--plant-fault).
LOSS_RTOL = 2.0 ** -10
#: The paper's precision requirement (16 bits), held by every matmul
#: route and product kernel.
MIN_BITS = 16.0
#: Floor of the composed inverse and of the inverse kernels: their
#: bf16 hi/lo operands carry 16 significand bits, and on 64 damped
#: 128x128 blocks they read 15.09 bits on v5e and on the CPU alike.
INV_MIN_BITS = 14.5

PHASE_TIMEOUT_S = {"train": 700, "serve": 300, "precision": 300,
                   "multichip": 1100}


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# -- child side ---------------------------------------------------------


def _tpu_device() -> dict:
    """Exit nonzero unless JAX runs on a TPU; else the device record."""
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; the JAX backend is "
                 f"{jax.default_backend()!r}")
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _compile_clock() -> dict:
    """Accumulates XLA backend compile seconds of this process."""
    import jax

    acc = {"s": 0.0}

    def listen(event, duration, **_):
        if "backend_compile" in event:
            acc["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return acc


def _peak_hbm() -> int:
    import jax

    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def _train(argv, devices=None) -> dict:
    """One ``repro.launch.train`` run in a fresh checkpoint directory
    (the loop restores any checkpoint it finds)."""
    from repro.launch import train

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ck:
        t0 = time.perf_counter()
        s = train.main(argv + ["--ckpt-dir", ck], devices=devices)
        wall = time.perf_counter() - t0
    losses = [float(h["loss"]) for h in s["history"]]
    ok = (s["recoveries"] == 0 and s["steps"] == len(losses)
          and all(math.isfinite(x) for x in losses))
    return {"ok": ok, "recoveries": s["recoveries"],
            "stragglers": s["stragglers"], "main_wall_s": wall,
            "step_wall_s": s["step_wall_s"], "losses": losses}


def phase_train(device: dict, opts) -> dict:
    clock = _compile_clock()
    rec = _train(TRAIN_ARGV + ["--lr", opts.lr])
    losses = rec["losses"]
    # it trains: some later loss is below the first
    rec["descends"] = len(losses) > 1 and min(losses[1:]) < losses[0]
    rec.update(ok=rec["ok"] and rec["descends"], lr=float(opts.lr),
               compile_s=clock["s"], peak_hbm_bytes=_peak_hbm())
    return rec


def phase_serve(device: dict, opts) -> dict:
    from repro.configs import get_config
    from repro.launch import serve
    from repro.serve import synthetic_trace

    clock = _compile_clock()
    args = serve.build_parser().parse_args(SERVE_ARGV)
    summary, done = serve.main(SERVE_ARGV)
    vocab = get_config(args.arch).vocab
    reqs, _ = synthetic_trace(vocab, args.requests, args.prompt_len,
                              args.gen, args.max_slots, seed=args.seed)
    finished = sorted(done) == [r.rid for r in reqs]
    budgets = finished and all(
        len(done[r.rid].tokens) == r.max_new_tokens
        or done[r.rid].finish_reason == "eos" for r in reqs)
    in_vocab = all(0 <= t < vocab for f in done.values()
                   for t in f.tokens)
    return {"ok": bool(finished and budgets and in_vocab),
            "requests": len(done), "finished_all": finished,
            "budgets_met": bool(budgets), "tokens_in_vocab": in_vocab,
            "generated_tokens": summary["generated_tokens"],
            "wall_s": summary["wall_s"],
            "prefill_s": summary["prefill_s"],
            "decode_s": summary["decode_s"],
            "compile_s": clock["s"], "peak_hbm_bytes": _peak_hbm(),
            "sample_tokens": summary["sample_tokens"]}


def _kernel_bits(a, lam, inv64, kcfg, rng) -> dict:
    """Each Pallas kernel at bs=128 on the chip, in bits against float64
    host results: the two inverse kernels on the phase's blocks, and the
    three product kernels on random operands."""
    import numpy as np

    from repro.core.precision_inv import achieved_bits
    from repro.kernels import ops

    f64 = np.float64
    inv_kw = dict(ns_iters=kcfg.ns_iters, taylor_terms=kcfg.taylor_terms,
                  refine_steps=kcfg.refine_steps)
    nb, n = a.shape[0], a.shape[-1]
    bits = {"neumann_inv": achieved_bits(
        np.asarray(ops.neumann_inv(a, lam, **inv_kw), f64), inv64)}

    act = rng.standard_normal((2048, 4, n)).astype(np.float32)
    gram = np.einsum("tbn,tbm->bnm", act.astype(f64), act.astype(f64)) \
        / act.shape[0]
    glam = kcfg.damping * np.trace(gram, axis1=1, axis2=2) / n + 1e-8
    bits["fused_gram_inv"] = achieved_bits(
        np.asarray(ops.fused_gram_inv(act, rel_damp=kcfg.damping,
                                      **inv_kw), f64),
        np.linalg.inv(gram + glam[:, None, None] * np.eye(n)))

    ai, g, gi = (rng.standard_normal((nb, n, n)).astype(np.float32)
                 for _ in range(3))
    bits["fused_precond"] = achieved_bits(
        np.asarray(ops.fused_precond(ai, g, gi)[0], f64),
        ai.astype(f64) @ g @ gi)

    x = rng.standard_normal((512, 896)).astype(np.float32)
    w = rng.standard_normal((896, 1024)).astype(np.float32)
    bits["bitslice_mm"] = achieved_bits(
        np.asarray(ops.bitslice_mm(x, w), f64), x.astype(f64) @ w)

    inv = inv64.astype(np.float32)
    v = (0.1 * rng.standard_normal((nb, 64, n))).astype(np.float32)
    decay, cscale = 0.95, 0.05
    m = (inv.astype(f64) + inv.transpose(0, 2, 1)) * (0.5 / decay)
    y = v.astype(f64) @ m
    z = np.linalg.solve(y @ v.transpose(0, 2, 1) + np.eye(64) / cscale, y)
    bits["smw_update"] = achieved_bits(
        np.asarray(ops.smw_update(inv, v, decay=decay, cscale=cscale), f64),
        m - np.einsum("nka,nkb->nab", y, z))
    return bits


#: Each kernel's floor: the inverses are held to the composed inverse's.
KERNEL_MIN_BITS = {"neumann_inv": INV_MIN_BITS,
                   "fused_gram_inv": INV_MIN_BITS,
                   "fused_precond": MIN_BITS, "bitslice_mm": MIN_BITS,
                   "smw_update": MIN_BITS}


def phase_precision(device: dict, opts) -> dict:
    """64 damped SPD 128x128 blocks: the trainer's composed-precision
    inversion on the chip, then the WU apply ``A^-1 G`` through the
    fp32 and hilo routes, then each Pallas kernel, in bits against
    float64 host results. Each route is held to the paper's 16 bits
    against the float64 product of the same chip-computed inverse;
    the inverse, and the apply against a float64 inverse that it
    bounds, to ``INV_MIN_BITS``."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kfac, quantize, soi
    from repro.core.kfac import KFACConfig
    from repro.core.precision_inv import achieved_bits

    nb, n = 64, 128
    rng = np.random.default_rng(0)
    x = rng.standard_normal((nb, n, 2 * n))
    a = (x @ x.transpose(0, 2, 1) / (2 * n)).astype(np.float32)
    kcfg = KFACConfig(block_size=n)
    lam = np.asarray(soi.tikhonov_damping(a, kcfg.damping))
    a64 = a.astype(np.float64) + lam[:, None, None].astype(np.float64) \
        * np.eye(n)
    inv64 = np.linalg.inv(a64)
    inv = np.asarray(jax.jit(functools.partial(
        kfac.invert_blocks_flat, cfg=kcfg))(a, lam), np.float64)
    g = rng.standard_normal((nb, n, n)).astype(np.float32)
    want = inv64 @ g.astype(np.float64)
    inv_ref = inv.astype(np.float32).astype(np.float64) \
        @ g.astype(np.float64)
    rec = {"blocks": nb, "n": n, "damping": kcfg.damping,
           "inverse_bits": achieved_bits(inv, inv64)}
    # what the fp32 route would give unpinned: the chip's default
    # matmul precision for fp32 operands
    unpinned = jax.jit(lambda x, y: jnp.einsum(
        "nij,njk->nik", x, y, preferred_element_type=jnp.float32))(
        inv.astype(np.float32), g)
    rec["unpinned_fp32_einsum_bits"] = achieved_bits(
        np.asarray(unpinned, np.float64), inv_ref)
    ok = rec["inverse_bits"] >= INV_MIN_BITS
    for route in ("fp32", "hilo"):
        out = jax.jit(functools.partial(
            quantize.lowp_einsum, "nij,njk->nik", precision=route))(
            inv.astype(np.float32), g)
        out = np.asarray(out, np.float64)
        # the route alone: against the float64 product of the same
        # chip-computed inverse
        rec[f"{route}_route_bits"] = achieved_bits(out, inv_ref)
        # the chip's inverse applied through the route, against the
        # float64 inverse's product: bounded by the inverse's bits
        rec[f"inverse_{route}_apply_bits"] = achieved_bits(out, want)
        ok = (ok and rec[f"{route}_route_bits"] >= MIN_BITS
              and rec[f"inverse_{route}_apply_bits"] >= INV_MIN_BITS)
    rec["kernel_bits"] = _kernel_bits(a, lam, inv64, kcfg, rng)
    rec["ok"] = ok and all(b >= KERNEL_MIN_BITS[k]
                           for k, b in rec["kernel_bits"].items())
    return rec


def _placement(mesh) -> dict:
    """Device ids and chip coordinates of ``mesh``, and whether every
    pair of devices adjacent along a mesh axis is an ICI neighbour."""
    import numpy as np

    devs = np.asarray(mesh.devices)
    coords = {d.id: tuple(getattr(d, "coords", ())) for d in devs.flat}
    nbr = True
    for ax in range(devs.ndim):
        a = np.moveaxis(devs, ax, 0)
        for i in range(a.shape[0] - 1):
            for d0, d1 in zip(a[i].flat, a[i + 1].flat):
                c0, c1 = coords[d0.id], coords[d1.id]
                if c0 and sum(abs(p - q) for p, q in zip(c0, c1)) != 1:
                    nbr = False
    return {"axes": dict(mesh.shape),
            "ids": np.vectorize(lambda d: d.id)(devs).tolist(),
            "coords": {str(k): list(v) for k, v in coords.items()},
            "neighbours": nbr}


@contextlib.contextmanager
def _owner_exchange_skipped():
    """A planted fault: every tiled all-gather hands back the caller's
    own shard in each slot, so no device sees the inverses or the
    ``A^-1 g`` intermediates another device owns."""
    import jax
    import jax.numpy as jnp

    real = jax.lax.all_gather

    def own_only(x, axis_name, *, axis=0, tiled=False, **_):
        names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        copies = [x] * math.prod(jax.lax.axis_size(a) for a in names)
        return (jnp.concatenate if tiled else jnp.stack)(copies, axis)

    jax.lax.all_gather = own_only
    try:
        yield
    finally:
        jax.lax.all_gather = real


def _rel_diffs(got, ref) -> list:
    return [abs(a - b) / abs(b) for a, b in zip(got, ref)]


def phase_multichip(device: dict, opts) -> dict:
    import jax

    from repro.runtime.elastic import elastic_mesh

    if device["count"] != 4:
        raise SystemExit(f"chip_smoke --chips 4: found {device['count']} "
                         f"devices")
    clock = _compile_clock()
    argv = MULTI_ARGV + ["--lr", opts.lr]
    dist_inv = argv + ["--model-parallel", "2", "--dist-inv"]
    runs = {
        "dp2_mp2_dist_inv": (dist_inv, None),
        "pp2_mp2": (argv + ["--pp", "2", "--model-parallel", "2"], None),
        "one_chip": (argv, jax.devices()[:1]),
    }
    rec = {"placement": {"dp2_mp2": _placement(elastic_mesh(2)),
                         "pp2_mp2": _placement(elastic_mesh(2, pp=2))},
           "loss_rtol": LOSS_RTOL}
    for name, (a, devices) in runs.items():
        rec[name] = _train(a, devices=devices)
    ref = rec["one_chip"]["losses"]
    ok = all(rec[k]["ok"] for k in runs) and all(
        p["neighbours"] for p in rec["placement"].values())
    for name in ("dp2_mp2_dist_inv", "pp2_mp2"):
        got = rec[name]["losses"]
        rel = _rel_diffs(got, ref)
        rec[name]["max_rel_loss_diff"] = max(rel) if rel else None
        ok = ok and len(got) == len(ref) and max(rel) <= LOSS_RTOL
    if opts.plant_fault:
        with _owner_exchange_skipped():
            fault = _train(dist_inv)
        rel = _rel_diffs(fault["losses"], ref)
        fault["max_rel_loss_diff"] = max(rel) if rel else None
        # the tolerance must catch it
        fault["caught"] = bool(rel) and max(rel) > LOSS_RTOL
        rec["dp2_mp2_dist_inv_no_exchange"] = fault
        ok = ok and fault["caught"]
    rec.update(ok=ok, compile_s=clock["s"], peak_hbm_bytes=_peak_hbm())
    return rec


PHASES = {"train": phase_train, "serve": phase_serve,
          "precision": phase_precision, "multichip": phase_multichip}


def run_phase(opts) -> None:
    device = _tpu_device()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    t0 = time.perf_counter()
    rec = PHASES[opts.phase](device, opts)
    emit({"phase": opts.phase, "device": device,
          "phase_wall_s": time.perf_counter() - t0, **rec})
    if not rec["ok"]:
        sys.exit(f"chip_smoke: phase {opts.phase} failed")


# -- parent side --------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--lr", default=LR,
                    help="step size of every training run")
    ap.add_argument("--plant-fault", action="store_true",
                    help="with --chips 4: also run the 2x2 --dist-inv "
                         "trainer with its owner exchange skipped, "
                         "which must miss the loss tolerance")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        run_phase(args)
        return 0
    phases = ("train", "serve", "precision") if args.chips == 1 \
        else ("multichip",)
    device = None
    for name in phases:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
               "--lr", args.lr] + (["--plant-fault"] if args.plant_fault
                                   else [])
        try:
            proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                                  timeout=PHASE_TIMEOUT_S[name])
        except subprocess.TimeoutExpired as e:
            out = e.stdout or ""
            sys.stdout.write(out if isinstance(out, str)
                             else out.decode(errors="replace"))
            print(f"chip_smoke: phase {name} timed out", file=sys.stderr)
            return 1
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        if proc.returncode != 0:
            print(f"chip_smoke: phase {name} exited {proc.returncode}",
                  file=sys.stderr)
            return 1
        device = json.loads(proc.stdout.strip().splitlines()[-1])["device"]
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
