"""The attention kernel's reader (``metrics/attn_kernel_ms_per_step.py``):
the name-stack rule, and the reader on two traces recorded on one TPU v5e
chip: one of the flash kernel (``data/smoke_kernel.xplane.pb.gz``, made
by ``record_kernel_trace.py`` from an empty compile cache, with the
result line that run printed), where it reads the kernel's share of
``attn_ms_per_step``, and one of the XLA path
(``data/smoke_scoped.xplane.pb.gz``), where it reads nothing."""

import gzip
import json
import os

import pytest

import harness
import record_kernel_trace
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(DATA)), "metrics")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAME = "attn_kernel_ms_per_step"


def _reader():
    return harness.load_module(os.path.join(METRICS, NAME + ".py"))


_FWD = ("jit(train_step)/jvp()/while/body/closed_call/attn/cond/"
        "branch_0_fun/flash_attention_fwd/pallas_call:")


@pytest.mark.parametrize("stack, mine", [
    (_FWD, True),
    (_FWD.replace("jvp()/", "transpose(jvp())/").replace(
        "closed_call/", "closed_call/checkpoint/rematted_computation/"),
     True),
    (_FWD.replace("fwd", "dkv") + ";" + _FWD, True),
    (_FWD[:-1], True),
    # the scores of the XLA path
    ("jit(train_step)/jvp()/while/body/closed_call/attn/"
     "bthgd,bshd->bhgts/dot_general:", False),
    # a Pallas kernel of another scope (the WU's)
    ("jit(train_step)/wu/fused_precond/pallas_call:", False),
    ("", False),
])
def test_kernel_stack_rule(stack, mine):
    assert _reader()._is_kernel(stack) is mine


def test_load_by_hand(tmp_path):
    """A trace by hand: the window [1, 5) s on the host; on the device the
    train program's kernel [0.5, 2), a loop [0.5, 4) whose name stack
    holds the kernel's, and the same kernel in the statistics program
    [2, 3). Only the train program's kernel, clipped to the window,
    counts: the loop encloses it, and it is not work of its own."""
    import scopes

    space = scopes._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bench:window"
    line = host.lines.add(name="main", timestamp_ns=1_000_000_000)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=4 * 10 ** 12)
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "program_id")):
        dev.stat_metadata.add(key=key).value.name = name
    ops = {10: ("jit_train_step(7)", None, None),
           11: ("jit_stats_step(8)", None, None),
           20: ("%custom-call.1 = f32[] custom-call()", 7, _FWD),
           21: ("%while.2 = f32[] while()", 7, _FWD),
           22: ("%custom-call.3 = f32[] custom-call()", 8,
                _FWD.replace("train_step", "stats_step"))}
    for key, (name, pid, stack) in ops.items():
        md = dev.event_metadata.add(key=key).value
        md.name = name
        if pid is not None:
            md.stats.add(metadata_id=2, uint64_value=pid)
        if stack is not None:
            md.stats.add(metadata_id=1, str_value=stack)
    s = 10 ** 12
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    mods.events.add(metadata_id=10, offset_ps=s // 2, duration_ps=7 * s // 2)
    mods.events.add(metadata_id=11, offset_ps=2 * s, duration_ps=s)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for key, a, b in ((20, 0.5, 2), (21, 0.5, 4), (22, 2, 3)):
        line.events.add(metadata_id=key, offset_ps=int(a * s),
                        duration_ps=int((b - a) * s))
    path = str(tmp_path / "hand.xplane.pb.gz")
    with gzip.open(path, "wb") as f:
        f.write(space.SerializeToString())
    assert _reader().load(path) == pytest.approx(1.0)


def _reading(trace_file, tmp_path, cell):
    path = tmp_path / (trace_file[:-3])
    with gzip.open(os.path.join(DATA, trace_file)) as f:
        path.write_bytes(f.read())
    # one cadence period: two train steps, one statistics pass, one
    # refresh (the recording scripts)
    win = harness.Window(steps=2, tokens=2 * 4 * 32, seconds=0.0, losses=[],
                         stats_calls=1, inv_calls=1, compiles=0)
    r = harness.make_reading(cell, 1, PEAKS, win, 0.0, 0,
                             trace=tr.reduce(tr.load(str(path))))
    r.trace_path = str(path)
    return r


def test_reads_nothing_on_the_xla_path(tmp_path):
    import smoke

    cell = smoke.smoke_cell("qwen1.5-0.5b.soi1024-exact-every2")
    r = _reading("smoke_scoped.xplane.pb.gz", tmp_path, cell)
    assert _reader().read(r) is None
    # without a trace at all, or with one that does not decode
    r.__dict__.pop("_attn_kernel_s")
    r.trace_path = str(tmp_path / "missing.xplane.pb")
    assert _reader().read(r) is None
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\xff" * 64)
    r.__dict__.pop("_attn_kernel_s")
    r.trace_path = str(bad)
    assert _reader().read(r) is None


def test_reads_the_kernel_on_the_chip_trace(tmp_path):
    """The reading the chip run printed comes out again, and it is a part
    of the attention scope's time, as the kernel is."""
    r = _reading("smoke_kernel.xplane.pb.gz", tmp_path,
                 record_kernel_trace.kernel_cell())
    with open(os.path.join(DATA, "smoke_kernel.result.json")) as f:
        res = json.load(f)
    got = _reader().read(r)
    assert got == pytest.approx(res["metrics"][NAME]["value"])
    attn = harness.load_module(os.path.join(
        METRICS, "attn_ms_per_step.py")).read(r)
    assert 0 < got < attn
