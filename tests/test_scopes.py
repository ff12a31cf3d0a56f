"""Where the device time of the K-FAC step can be told apart: the named
scopes of the train, statistics and refresh programs (the op metadata a
device profile carries as each op's name stack), and the ``phase:sync``
span around the step's host sync.

The programs are the ones ``KFACProgram.make_step`` dispatches, at
smoke size, compiled for the CPU: the op names are what XLA keeps for
any backend.
"""

import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.configs import get_smoke_config
from repro.core.kfac import KFACConfig
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_dev_mesh
from repro.launch.train import KFACProgram

#: scope -> the program of ``KFACProgram.programs`` that carries it,
#: and those that must not (the statistics pass runs the model too)
SCOPES = {"attn": "train", "mlp": "train", "head": "train", "wu": "train",
          "soi_gram": "stats", "inv": "inv"}
ABSENT = {"attn": ("inv",), "mlp": ("inv",), "head": ("inv",),
          "wu": ("stats", "inv"), "soi_gram": ("train", "inv"),
          "inv": ("train", "stats")}


def _program(obs=None, **kw):
    cfg = get_smoke_config("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, soi_block=min(32, cfg.soi_block))
    kcfg = KFACConfig(block_size=cfg.soi_block, stats_every=2, inv_every=2,
                      stats_batch=2, stats_seq=16, inv_method="exact")
    return cfg, kcfg, KFACProgram(cfg, kcfg, obs=obs, **kw)


def _in_stack(scope, op_name):
    """``scope`` is a component of the name stack, bare or under a
    transformation (``jvp(head)``, ``transpose(jvp(head))``). Ops named
    after an argument (``state.kfac.inverses['layers/attn/wq']``) carry
    no name stack."""
    stack = op_name.split(";")[0]
    return stack.startswith("jit(") and any(
        re.fullmatch(r"(?:\w+\()*%s\)*" % scope, c)
        and not c.startswith("jit(") for c in stack.split("/"))


@pytest.fixture(scope="module")
def op_names():
    from jax.experimental.compilation_cache import compilation_cache

    cfg, kcfg, prog = _program()
    mesh = make_dev_mesh(1)
    # the persistent cache's key leaves the op metadata out: an entry
    # compiled before a scope existed would come back without it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.set_mesh(mesh):
            prog.make_step(mesh)
            ab = steps_mod.abstract_train_state(cfg, kcfg)
            batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
            p = prog.programs
            lowered = {"train": p["train"].lower(ab, batch),
                       "stats": p["stats"].lower(ab, batch),
                       "inv": p["inv"].lower(ab.kfac.factors,
                                             ab.kfac.inverses)}
            return {k: re.findall(r'op_name="([^"]*)"',
                                  lo.compile().as_text())
                    for k, lo in lowered.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("scope", sorted(SCOPES))
def test_scope_names_ops_of_its_program(op_names, scope):
    names = op_names[SCOPES[scope]]
    mine = [n for n in names if _in_stack(scope, n)]
    assert mine, f"no op of the {SCOPES[scope]} program under {scope!r}"
    if scope in ("attn", "mlp"):
        # the backward pass (and its recomputed forward) keeps the scope
        assert any("/transpose(" in n for n in mine), mine[:5]
        assert any("/rematted_computation/" in n for n in mine)
    for prog in ABSENT[scope]:
        assert not any(_in_stack(scope, n) for n in op_names[prog]), prog


def _spans(monkeypatch):
    names = []
    real = obs_mod.Tracer.span

    def span(self, name, *a, **kw):
        names.append(name)
        return real(self, name, *a, **kw)

    monkeypatch.setattr(obs_mod.Tracer, "span", span)
    return names


@pytest.mark.parametrize("smw", [False, True])
def test_step_records_sync_span_only_when_enabled(monkeypatch, smw):
    names = _spans(monkeypatch)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    mesh = make_dev_mesh(1)
    recorded = {}
    for label, obs in (("null", obs_mod.NULL),
                       ("on", obs_mod.Observability(enabled=True))):
        _, _, prog = _program(obs=obs, smw=smw)
        with jax.set_mesh(mesh):
            state = prog.init_state(mesh)
            step = prog.make_step(mesh)
            for _ in range(3):
                state, _ = step(state, batch)
            jax.block_until_ready(state)
        recorded[label] = list(names)
        names.clear()
        if label == "on":
            events = [e["name"] for e in obs.tracer.to_chrome()
                      ["traceEvents"]]
    assert recorded["null"] == []
    assert len(obs_mod.NULL.tracer) == 0
    # one sync per step; the SMW gate first reads a drift at step 3
    # (step 1 falls back and drops its drift, step 2 dispatches one)
    assert recorded["on"].count("phase:sync") == (1 if smw else 3)
    assert events.count("phase:sync") == (1 if smw else 3)
    assert "phase:train" in events
