"""Staleness-tolerant double-buffered inverse refresh.

RePAST runs its INV crossbar groups *concurrently* with the FP/BP/WU
pipelines: the SOI inverses a training step consumes are the ones the
INV engine finished last cadence, not ones computed synchronously in
the step (Sec. IV-B / Fig. 8). The TPU image: at each ``inv_every``
trigger the refresher (1) swaps in the refresh dispatched at the
*previous* trigger — so step N preconditions with inverses of the
factors as of step N - inv_every — and (2) dispatches the next refresh
from the current factors as an independent computation. JAX's async
dispatch lets that refresh overlap the following train steps instead of
serializing with them.

Double buffering: exactly one refresh is ever in flight; the buffers it
writes are the ones just retired from the optimizer state (the
``refresh_into(factors, retired_buffers)`` form donates them), so the
steady state rotates two inverse-tree allocations.

K-FAC's tolerance to this one-cadence staleness is the same property
the paper leans on when it amortizes SOI updates over 10 batches: the
factors move slowly relative to the parameters.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional


def _null_cm():
    return contextlib.nullcontext()


class AsyncInverseRefresher:
    """Drives ``state.inverses`` from lagged, overlapped refreshes.

    ``refresh_fn(factors) -> inverses`` computes a full inverse tree;
    ``refresh_into(factors, buffers) -> inverses`` is a donated variant
    that may reuse ``buffers`` (the inverse tree being retired) for its
    output. At least one must be given; production passes only
    ``refresh_into`` + ``spare_buffers`` so exactly one jitted program
    ever exists.

    The host object is deliberately tiny: all heavy work stays inside
    the injected (jitted) callables, and the only state is the pending
    (in-flight) inverse tree.

    ``spare_buffers`` (an inverse-tree of scratch arrays) seeds the
    double buffer: with it, the *first* dispatch already goes through
    ``refresh_into``, so only one jitted program ever exists and it
    compiles at the first trigger (step 0, inside the step-watchdog's
    warmup window) — without it the donated variant would first compile
    at the second trigger, mid-training, and a multi-second compile
    inside an armed watchdog deadline reads as a hung step.
    """

    def __init__(self, refresh_fn: Optional[Callable[[Any], Any]] = None,
                 refresh_into: Optional[Callable[[Any, Any], Any]] = None,
                 spare_buffers: Any = None, obs: Any = None):
        if refresh_fn is None and refresh_into is None:
            raise ValueError(
                "need refresh_fn and/or refresh_into(+spare_buffers)")
        self.refresh_fn = refresh_fn
        self.refresh_into = refresh_into
        self._spare = spare_buffers
        self._pending: Any = None
        self.n_dispatched = 0
        self.n_swapped = 0
        self._obs = obs
        self._c_dispatch = self._c_swap = None
        if obs is not None and getattr(obs, "enabled", False):
            self._c_dispatch = obs.counter(
                "solve_inv_dispatch_total",
                "async inverse refreshes dispatched")
            self._c_swap = obs.counter(
                "solve_inv_swap_total",
                "lagged inverse trees swapped into the live state")

    @property
    def has_pending(self) -> bool:
        return self._pending is not None

    def step(self, kstate):
        """One inv-cadence trigger: swap in the previous refresh (if
        any), dispatch the next one. Returns the updated state; does not
        block on the dispatched computation."""
        retired = None
        if self._pending is not None:
            retired = kstate.inverses
            kstate = kstate._replace(inverses=self._pending)
            self._pending = None
            self.n_swapped += 1
            if self._c_swap is not None:
                self._c_swap.inc()
        if retired is None:
            retired, self._spare = self._spare, None
        # dispatch-timed span: the refresh is *meant* to overlap the
        # following train steps, so fencing here would be a lie about
        # the design (and would serialize the overlap it measures)
        span = self._obs.span("inv_refresh_dispatch") \
            if self._c_dispatch is not None else _null_cm()
        with span:
            if retired is not None and self.refresh_into is not None:
                self._pending = self.refresh_into(kstate.factors,
                                                  retired)
            else:
                if self.refresh_fn is None:
                    # donated-only configuration must never silently
                    # fall back to a second (uncompiled) program
                    # mid-training
                    raise RuntimeError(
                        "refresh_into has no retired/spare buffers and "
                        "no refresh_fn fallback was provided")
                self._pending = self.refresh_fn(kstate.factors)
        self.n_dispatched += 1
        if self._c_dispatch is not None:
            self._c_dispatch.inc()
        return kstate

    def peek(self, kstate):
        """State with any in-flight refresh folded in, *without*
        consuming it — for checkpoint snapshots, so checkpoint cadence
        never perturbs the live training trajectory (the pending swap
        still happens at its own trigger)."""
        if self._pending is not None:
            return kstate._replace(inverses=self._pending)
        return kstate

    def flush(self, kstate):
        """Fold any in-flight refresh into the state (end-of-run
        barrier), leaving nothing pending. The displaced inverse tree
        re-seeds the spare so a later ``step()`` still runs the donated
        program (never a cold second program mid-training)."""
        if self._pending is not None:
            if self._spare is None:
                self._spare = kstate.inverses
            kstate = kstate._replace(inverses=self._pending)
            self._pending = None
            self.n_swapped += 1
        return kstate

    def reset(self) -> None:
        """Drop the in-flight refresh (elastic recovery: the restored
        state's factors no longer match what was dispatched). The
        dropped tree is retained as the spare — its values are garbage
        but as a donation target it keeps a donated-only refresher
        functional if it is reused rather than rebuilt."""
        if self._pending is not None and self._spare is None:
            self._spare = self._pending
        self._pending = None


class SMWRefresher:
    """Every-step incremental (SMW) refresh with a drift-gated fallback.

    The anti-thesis of ``AsyncInverseRefresher``: instead of tolerating
    a one-cadence staleness window, the rank-k Woodbury path
    (``repro.solve.smw``) is cheap enough to refresh the inverses inside
    *every* step's fused program — nothing is ever in flight, nothing is
    ever stale. What replaces the staleness budget is a *drift* budget:
    ``smw_step(state, batch) -> (state, metrics)`` carries a probe
    residual in ``metrics["smw_drift"]`` and when it exceeds
    ``drift_budget`` the host re-inverts fully through ``refresh_into``
    — the same donated program the double-buffered path uses, so the
    fallback costs one allocation rotation, not a new compile.

    Two deliberate asymmetries with the async refresher:

    * the drift readback is one step LAGGED — the scalar dispatched at
      step N is ``float()``-ed at step N+1, so the host never blocks on
      the computation it just dispatched (the same async-dispatch
      overlap the double buffer exists for, bought with one step of
      fallback latency instead of a whole cadence of staleness);
    * the FIRST step always falls back: it seeds real inverses over the
      ``init_inverses`` identities (an SMW update of an identity tracks
      nothing) and compiles the donated program inside the step-0
      watchdog warmup window, mirroring the ``spare_buffers`` rationale
      above.

    ``peek``/``reset`` keep the TrainLoop hook surface of the async
    refresher so ``launch.train`` can hold either behind one attribute.
    """

    def __init__(self, smw_step: Callable[[Any, Any], Any],
                 refresh_into: Callable[[Any, Any], Any],
                 drift_budget: float, obs: Any = None):
        self.smw_step = smw_step
        self.refresh_into = refresh_into
        self.drift_budget = float(drift_budget)
        self._drift: Any = None          # scalar dispatched last step
        self.n_steps = 0
        self.n_fallbacks = 0
        self.last_drift = float("nan")
        self._obs = obs
        self._g_drift = self._c_fallback = None
        if obs is not None and getattr(obs, "enabled", False):
            self._g_drift = obs.gauge(
                "solve_smw_drift",
                "lagged SMW probe residual (gate input)")
            self._c_fallback = obs.counter(
                "solve_smw_fallback_total",
                "full re-inversions triggered by the drift gate "
                "(incl. the seeding step-0 fallback)")

    def step(self, state, batch):
        """One training step's refresh: run the fused SMW program, then
        apply the (lagged) drift gate. Returns ``(state, metrics)``."""
        state, metrics = self.smw_step(state, batch)
        fallback = self.n_steps == 0
        if self._drift is not None:
            # the host gate: the one wait on the device in an SMW step
            span = self._obs.span("phase:sync", cat="sync") \
                if self._g_drift is not None else _null_cm()
            with span:
                d = float(self._drift)   # blocks on *last* step only
            self.last_drift = d
            if self._g_drift is not None:
                self._g_drift.set(d)
            if not (d <= self.drift_budget):   # NaN drift must trigger
                fallback = True
        self._drift = metrics.get("smw_drift")
        self.n_steps += 1
        if fallback:
            kst = state.kfac
            state = state._replace(kfac=kst._replace(
                inverses=self.refresh_into(kst.factors, kst.inverses)))
            self.n_fallbacks += 1
            if self._c_fallback is not None:
                self._c_fallback.inc()
                self._obs.event("smw_fallback", step=self.n_steps - 1,
                                drift=self.last_drift)
            # the pending drift was measured on the inverses we just
            # replaced — reading it next step would re-trigger for free
            self._drift = None
        metrics["smw_fallback"] = 1.0 if fallback else 0.0
        return state, metrics

    def peek(self, kstate):
        """Nothing is ever in flight on this path; checkpoints see the
        live state as-is."""
        return kstate

    def flush(self, kstate):
        return kstate

    def reset(self) -> None:
        """Elastic recovery: the restored state's drift scalar is gone;
        force the next step to fall back (cheap) rather than trust an
        un-probed inverse tree."""
        self._drift = None
        self.n_steps = 0
