"""Fault-tolerance: watchdog behavior, elastic mesh, and the full
checkpoint-restore-continue loop with injected failures."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.runtime import DeviceLoss, StepWatchdog, largest_mesh
from repro.runtime.watchdog import StepDeadlineExceeded


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_flags_straggler():
    wd = StepWatchdog(straggler_factor=2.0, warmup_steps=1, window=8)
    for _ in range(4):
        with wd.step():
            time.sleep(0.01)
    with wd.step():
        time.sleep(0.05)
    assert wd.last_was_straggler
    assert wd.n_stragglers == 1
    # straggler did not pollute the healthy window
    assert wd.median() < 0.03


def test_watchdog_deadline_raises():
    wd = StepWatchdog(hang_factor=2.0, warmup_steps=1,
                      hard_deadline_s=0.03)
    with pytest.raises(StepDeadlineExceeded):
        with wd.step():
            time.sleep(0.06)


# ---------------------------------------------------------------------------
# elastic mesh math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,model,expect", [
    (256, 16, (16, 16)),
    (255, 16, (8, 16)),       # lost a chip: data halves to pow2
    (512, 16, (32, 16)),
    (8, 4, (2, 4)),
    (7, 4, (1, 4)),
])
def test_largest_mesh(n, model, expect):
    assert largest_mesh(n, model) == expect


def test_largest_mesh_impossible():
    with pytest.raises(DeviceLoss):
        largest_mesh(3, 4)


# ---------------------------------------------------------------------------
# end-to-end loop: failure -> restore -> continue, exactly-once data
# ---------------------------------------------------------------------------

class ToyProgram:
    """Counts data it consumed so we can assert exactly-once replay."""

    def init_state(self, mesh):
        return {"w": jnp.zeros((4,)), "seen": jnp.zeros((), jnp.int32)}

    def make_step(self, mesh):
        @jax.jit
        def step(state, batch):
            s = jnp.sum(batch["tokens"][:, 0]).astype(jnp.float32)
            return (
                {"w": state["w"] + s, "seen": state["seen"] + 1},
                {"loss": s},
            )
        return step

    def state_sharding(self, mesh):
        return lambda key: None


def _run(tmp_path, inject=None, total=12):
    from repro.data import SyntheticTokens
    from repro.runtime import LoopConfig, TrainLoop

    ds = SyntheticTokens(vocab=97, seq_len=8, global_batch=4, seed=3)
    loop = TrainLoop(
        LoopConfig(total_steps=total, ckpt_dir=str(tmp_path / "ck"),
                   ckpt_every=4, log_every=1, max_failures=3),
        ToyProgram(), ds, inject=inject)
    return loop, loop.run()


def test_loop_completes_and_checkpoints(tmp_path):
    loop, summary = _run(tmp_path)
    assert summary["steps"] == 12
    assert summary["recoveries"] == 0
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path / "ck")) == 12


def test_loop_recovers_from_injected_failure(tmp_path):
    fired = []

    def inject(step):
        if step == 6 and not fired:
            fired.append(step)
            raise DeviceLoss(0, "drill")

    loop, summary = _run(tmp_path, inject=inject)
    assert summary["steps"] == 12
    assert summary["recoveries"] == 1


def test_loop_exactly_once_data(tmp_path):
    """State after a mid-run failure equals a clean run's state: the
    restored cursor replays the stream with no skips or repeats."""
    _, clean = _run(tmp_path / "a")
    fired = []

    def inject(step):
        if step == 7 and not fired:
            fired.append(step)
            raise DeviceLoss(0, "drill")

    loop_b, failed = _run(tmp_path / "b", inject=inject)
    from repro.checkpoint import restore
    sa, _ = restore(str(tmp_path / "a" / "ck"), ToyProgram()
                    .init_state(None))
    sb, _ = restore(str(tmp_path / "b" / "ck"), ToyProgram()
                    .init_state(None))
    np.testing.assert_allclose(np.asarray(sa["w"]), np.asarray(sb["w"]))
    assert int(sb["seen"]) == 12


def test_loop_gives_up_after_max_failures(tmp_path):
    def inject(step):
        raise DeviceLoss(0, "permanent")

    with pytest.raises(DeviceLoss):
        _run(tmp_path, inject=inject)
    # the loop's mesh does not outlive the failed run
    from repro.dist.api import active_mesh
    assert active_mesh() is None


# ---------------------------------------------------------------------------
# recovery classification: only known failure classes restore
# ---------------------------------------------------------------------------

def test_recoverable_classification_table():
    from repro.runtime.loop import _recoverable

    XlaErr = jax.errors.JaxRuntimeError

    # the repo's own fault types restore
    assert _recoverable(DeviceLoss(0, "drill"))
    assert _recoverable(StepDeadlineExceeded("hang"))
    # ordinary programming errors must re-raise, even when their
    # message happens to contain both "device" and "error" (the old
    # heuristic looped checkpoint-restore over these)
    assert not _recoverable(ValueError(
        "device mesh error: axis 'model' not found"))
    assert not _recoverable(TypeError("cannot add device error type"))
    assert not _recoverable(KeyError("layers/0/attn"))
    # sick-device markers only count on XLA runtime errors
    assert not _recoverable(RuntimeError("RESOURCE_EXHAUSTED: fake"))
    assert _recoverable(XlaErr(
        "RESOURCE_EXHAUSTED: out of memory allocating 1g"))
    assert _recoverable(XlaErr("DATA_LOSS: checkpoint shard lost"))
    assert _recoverable(XlaErr("UNAVAILABLE: slice health check"))
    assert not _recoverable(XlaErr(
        "INVALID_ARGUMENT: mismatched shapes"))


def test_loop_raises_on_programming_error(tmp_path):
    """A bug whose message contains 'device'+'error' must surface, not
    spin the restore loop (regression for the old heuristic)."""
    from repro.data import SyntheticTokens
    from repro.runtime import LoopConfig, TrainLoop

    def inject(step):
        if step == 2:
            raise ValueError("device layout error: bad spec")

    ds = SyntheticTokens(vocab=97, seq_len=8, global_batch=4, seed=3)
    loop = TrainLoop(
        LoopConfig(total_steps=8, ckpt_dir=str(tmp_path / "ck"),
                   ckpt_every=4, log_every=1, max_failures=3),
        ToyProgram(), ds, inject=inject)
    with pytest.raises(ValueError):
        loop.run()
    # and it must fail fast: zero checkpoint-restore cycles burned
    assert loop.n_recoveries == 0


# ---------------------------------------------------------------------------
# straggler accounting survives recovery
# ---------------------------------------------------------------------------

def test_watchdog_reset_window_keeps_counters():
    wd = StepWatchdog(straggler_factor=2.0, warmup_steps=1, window=8)
    for _ in range(3):
        with wd.step():
            time.sleep(0.01)
    with wd.step():
        time.sleep(0.05)
    assert wd.n_stragglers == 1
    n_steps = wd.n_steps
    wd.reset_window()
    # cumulative counters survive; the timing window (and thus the
    # deadline) is back in warmup so a slow recompile step cannot trip
    assert wd.n_stragglers == 1
    assert wd.n_steps == n_steps
    assert wd.median() is None
    with wd.step():
        time.sleep(0.05)             # slow, but window is warming up
    assert wd.n_stragglers == 1


def test_loop_straggler_count_survives_recovery(tmp_path):
    """The final report must accumulate straggler counts across
    recoveries (a fresh watchdog used to zero them)."""
    fired = []

    def inject(step):
        if step == 5 and not fired:
            fired.append(step)
            raise DeviceLoss(0, "drill")

    from repro.data import SyntheticTokens
    from repro.runtime import LoopConfig, TrainLoop

    ds = SyntheticTokens(vocab=97, seq_len=8, global_batch=4, seed=3)
    loop = TrainLoop(
        LoopConfig(total_steps=10, ckpt_dir=str(tmp_path / "ck"),
                   ckpt_every=4, log_every=1, max_failures=3),
        ToyProgram(), ds, inject=inject)
    # simulate stragglers observed before the failure
    loop.watchdog.n_stragglers = 2
    summary = loop.run()
    assert summary["recoveries"] == 1
    assert summary["stragglers"] >= 2
