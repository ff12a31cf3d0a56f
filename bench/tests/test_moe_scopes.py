"""The reader of the expert-share model's scopes (``moe_scopes.py``): its
names and kernel tag, that it leaves ``scopes`` with its own six names,
and, on a trace recorded on one TPU v5e chip
(``data/smoke_kernel.xplane.pb.gz``), that it reads each of ``scopes``'
scopes as ``scopes.load`` does and the flash kernel's calls as
``attn_kernel_ms_per_step`` does."""

import os

import pytest

import harness
import moe_scopes
import scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(DATA)), "metrics")
_LAYER = "jit(train_step)/jvp()/while/body/closed_call/"


@pytest.mark.parametrize("stack, want", [
    (_LAYER + "mla/dot_general:", "mla"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "mla/cond/branch_0_fun/flash_attention_dq/pallas_call:", "mla/kernel"),
    (_LAYER + "moe_route/sort:", "moe_route"),
    (_LAYER + "moe_route/moe_experts/pallas_call:", "moe_experts/kernel"),
    ("jit(stats_step)/jvp()/expert_gram/pallas_call:",
     "expert_gram/kernel"),
    (_LAYER + "attn/dot_general:", "attn"),
    (_LAYER + "dot_general:", None),
])
def test_scope_of(stack, want):
    assert moe_scopes.scope_of(stack) == want
    assert scopes.scope_of(_LAYER + "mla/dot_general:") is None
    assert scopes._COMPONENT.pattern.count("|") == len(scopes.SCOPES) - 1


def test_recorded_trace_reads_as_scopes_and_the_kernel_reader():
    path = os.path.join(DATA, "smoke_kernel.xplane.pb.gz")
    got, base = moe_scopes.load(path), scopes.load(path)
    assert scopes.scope_of is moe_scopes._scope_of
    for program, by_scope in base.items():
        for scope, seconds in by_scope.items():
            assert got[program][scope] == pytest.approx(seconds, rel=1e-9)
    kernel = harness.load_module(os.path.join(
        METRICS, "attn_kernel_ms_per_step.py")).load(path)
    assert kernel and got["jit_train_step"]["attn/kernel"] == \
        pytest.approx(kernel, rel=1e-9)
