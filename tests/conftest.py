"""Test-session bootstrap.

1. Make ``repro`` importable from the src/ layout even without
   ``PYTHONPATH=src`` or an editable install.
2. If the real ``hypothesis`` package is unavailable in the container,
   register a minimal deterministic stand-in that supports the subset
   used by this suite (``given``/``settings`` and the ``integers`` /
   ``floats`` / ``sampled_from`` / ``booleans`` strategies). It runs
   ``max_examples`` seeded random examples per test — no shrinking, no
   database — which keeps the property tests meaningful without adding
   a dependency the image doesn't bake in. CI installs the real
   package (``pip install -e ".[test]"``), so there the stub is dormant;
   ``tests/test_hypothesis_stub.py`` keeps both code paths green.
3. Provide the ``multidevice`` marker + subprocess runner for tests
   that need a forced multi-device host platform
   (``XLA_FLAGS=--xla_force_host_platform_device_count=4``). jax fixes
   its device count at backend init, so those tests only run when the
   session already has >= 4 devices (the dedicated CI job, or the
   in-suite subprocess smoke that re-launches pytest with the flag set
   — the same pattern as launch/dryrun.py and
   benchmarks/grad_compression.py).
"""

from __future__ import annotations

import os
import random
import subprocess
import sys
import types

import pytest

_SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src"))
_ROOT = os.path.dirname(_SRC)

try:
    import repro  # noqa: F401
except ImportError:
    sys.path.insert(0, _SRC)


def make_hypothesis_stub():
    """Build (but do not install) the deterministic hypothesis stand-in.

    Returns ``(mod, st)`` mirroring ``hypothesis`` /
    ``hypothesis.strategies``. Exposed so the stub-vs-real parity smoke
    can exercise this implementation even when the real package is
    installed.
    """

    class _Strategy:
        def __init__(self, fn):
            self._fn = fn

        def example(self, rng):
            return self._fn(rng)

    def integers(min_value, max_value):
        return _Strategy(lambda r: r.randint(min_value, max_value))

    def floats(min_value, max_value, **_kw):
        def draw(r):
            # endpoints with small probability; uniform otherwise
            u = r.random()
            if u < 0.05:
                return min_value
            if u < 0.1:
                return max_value
            return r.uniform(min_value, max_value)
        return _Strategy(draw)

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda r: r.choice(elements))

    def booleans():
        return _Strategy(lambda r: bool(r.getrandbits(1)))

    def just(value):
        return _Strategy(lambda r: value)

    class _Rejected(Exception):
        pass

    def assume(condition):
        if not condition:
            raise _Rejected()
        return True

    def settings(max_examples=10, deadline=None, **_kw):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn
        return deco

    def given(*arg_strategies, **kw_strategies):
        def deco(fn):
            def runner(*args, **kwargs):
                n = getattr(runner, "_stub_max_examples", None) \
                    or getattr(fn, "_stub_max_examples", 10)
                rng = random.Random(fn.__qualname__)
                done = 0
                attempts = 0
                while done < n and attempts < 20 * n:
                    attempts += 1
                    vals = [s.example(rng) for s in arg_strategies]
                    kvals = {k: s.example(rng)
                             for k, s in kw_strategies.items()}
                    try:
                        fn(*args, *vals, **kwargs, **kvals)
                    except _Rejected:
                        continue
                    done += 1

            # keep a fixture-free (*args) signature for pytest while
            # preserving identity and any marks
            runner.__name__ = fn.__name__
            runner.__qualname__ = fn.__qualname__
            runner.__module__ = fn.__module__
            runner.__doc__ = fn.__doc__
            if hasattr(fn, "pytestmark"):
                runner.pytestmark = fn.pytestmark
            if hasattr(fn, "_stub_max_examples"):
                runner._stub_max_examples = fn._stub_max_examples
            return runner
        return deco

    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.assume = assume
    mod.__version__ = "0.0-stub"
    st = types.ModuleType("hypothesis.strategies")
    st.integers = integers
    st.floats = floats
    st.sampled_from = sampled_from
    st.booleans = booleans
    st.just = just
    mod.strategies = st
    return mod, st


def _install_hypothesis_stub():
    mod, st = make_hypothesis_stub()
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st


try:
    import hypothesis  # noqa: F401
except ImportError:
    _install_hypothesis_stub()


# ---------------------------------------------------------------------------
# multi-device marker + subprocess runner
# ---------------------------------------------------------------------------

# the marker itself is registered once, in pyproject.toml
# [tool.pytest.ini_options].markers
MULTIDEV_COUNT = 4


def pytest_collection_modifyitems(config, items):
    import jax

    if jax.device_count() >= MULTIDEV_COUNT:
        return
    skip = pytest.mark.skip(
        reason=f"needs {MULTIDEV_COUNT} devices (re-run under "
               f"XLA_FLAGS=--xla_force_host_platform_device_count="
               f"{MULTIDEV_COUNT})")
    for item in items:
        if "multidevice" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def multidev_runner():
    """Run pytest in a child process with a forced N-device host
    platform (jax pins its device count at init, so in-process tests
    cannot change it — same subprocess pattern as launch/dryrun.py)."""

    def run(pytest_args, ndev: int = MULTIDEV_COUNT):
        env = {**os.environ,
               "XLA_FLAGS":
                   f"--xla_force_host_platform_device_count={ndev}",
               "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": _SRC + os.pathsep
                   + os.environ.get("PYTHONPATH", "")}
        return subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             *pytest_args],
            capture_output=True, text=True, timeout=1200, cwd=_ROOT,
            env=env)

    return run
