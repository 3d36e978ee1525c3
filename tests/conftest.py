"""Shared fixtures. NOTE: no XLA_FLAGS here -- smoke tests and benches
must see the 1 real device; multi-device tests spawn subprocesses that
set --xla_force_host_platform_device_count themselves.

When ``hypothesis`` is not installed, a tiny deterministic fallback shim
is registered in its place (conftest loads before test-module collection)
so the property tests still run -- each ``@given`` draws ``max_examples``
seeded-random samples instead of shrinking counterexamples."""

import functools
import inspect
import os
import random
import subprocess
import sys
import types

import numpy as np
import pytest

try:
    import hypothesis  # noqa: F401
except ImportError:

    class _Strategy:
        def __init__(self, sample):
            self.sample = sample  # sample(rng) -> value

    def _integers(min_value=0, max_value=1 << 30):
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    def _sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rng: elements[rng.randrange(len(elements))])

    def _booleans():
        return _Strategy(lambda rng: bool(rng.randrange(2)))

    def _floats(min_value=0.0, max_value=1.0, **_):
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    def _given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_hyp_max_examples", 10)
                rng = random.Random(f"{fn.__module__}.{fn.__name__}")
                for _ in range(n):
                    drawn = {k: s.sample(rng) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)

            # pytest must only see the non-strategy params (fixtures), not
            # the drawn ones -- and must not unwrap to the original fn.
            del wrapper.__wrapped__
            sig = inspect.signature(fn)
            wrapper.__signature__ = sig.replace(
                parameters=[p for name, p in sig.parameters.items() if name not in strategies]
            )
            return wrapper

        return deco

    def _settings(max_examples=10, **_):
        def deco(fn):
            fn._hyp_max_examples = max_examples
            return fn

        return deco

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.sampled_from = _sampled_from
    _st.booleans = _booleans
    _st.floats = _floats
    _hyp.strategies = _st
    _hyp.__version__ = "0.0.0-shim"
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# The measured planner auto-runs a ppermute calibration sweep once per
# device kind on a fresh race (default-on in production). Under test it
# would add real-fabric timing noise to every measure-planner test and
# couple test outcomes to suite order, so the suite pins it off --
# subprocesses spawned by run_subprocess inherit the env and stay
# deterministic too. Calibration-specific tests inject timers or call
# planner.ensure_calibrated explicitly.
os.environ.setdefault("REPRO_AUTO_CALIBRATE", "0")


def run_subprocess(code: str, *, devices: int = 8, timeout: int = 600) -> str:
    """Run a python snippet with N host-platform devices; returns stdout.
    The snippet should print 'PASS' lines / raise on failure."""
    env = dict(os.environ)
    # a CPU rehearsal: the child never reaches for an accelerator, which
    # a parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=REPO,
    )
    if out.returncode != 0:
        raise AssertionError(f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}")
    return out.stdout


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
