#!/usr/bin/env python3
"""The control and the planted faults that the check must catch.

    python bench/faults.py --workload fft2_16k_p1 --fault control --seeds 1 2 3

Each fault patches the program under the harness for one run; the run
is otherwise the benchmark's own (``run.run_cell``), and prints the
numbers compared with their limits. The benchmark's runs never import
this file.

``control``
    The float64 reference put in the program's place, computed in the
    precision below the configuration's complex64: inputs and answers
    rounded to bfloat16 around XLA's FFT (which has no bfloat16
    arithmetic of its own).
``state_unchanged``
    The transform returns its input.
``no_exchange``
    The all-to-all leaves every block on its own chip (P > 1 only).
``altered_answer``
    Element [0, 0] of each answer moved by 1% of the answer's norm.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
FAULTS = ("control", "state_unchanged", "no_exchange", "altered_answer")


def _bf16(x):
    """``x`` rounded to bfloat16's precision. ``reduce_precision`` and not
    a cast there and back, which XLA may drop as excess precision."""
    import jax.numpy as jnp
    from jax import lax

    def rounded(a):
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    if jnp.iscomplexobj(x):
        return lax.complex(rounded(x.real), rounded(x.imag))
    return rounded(x)


def _control_transform(x):
    """The reference in bfloat16, in the slab layout (last two axes swapped)."""
    import jax.numpy as jnp

    return _bf16(jnp.swapaxes(jnp.fft.fft2(_bf16(x)), -1, -2))


def _altered(y):
    import jax.numpy as jnp

    idx = (...,) + (0,) * 2
    return y.at[idx].add(0.01 * jnp.linalg.norm(y))


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def planted(fault: str, system: str):
    """Plant ``fault`` in the program for the ``with`` block."""
    import jax

    from repro.core import plan as plan_mod
    from repro.core import transpose as tr

    stack = contextlib.ExitStack()
    with stack:
        if fault == "no_exchange":
            import jax.numpy as jnp
            from jax import lax

            def local_only(x, axis_name):
                p = lax.axis_size(axis_name)
                return tr._transpose_local(jnp.concatenate(jnp.split(x, p, axis=-1), axis=-2))

            stack.enter_context(_patched(tr, "_alltoall", local_only))
        elif system == "plan_fft":
            execute = plan_mod.Plan.execute
            if fault == "control":
                fn = jax.jit(_control_transform)
                new = lambda self, x: fn(x)  # noqa: E731
            elif fault == "state_unchanged":
                new = lambda self, x: x  # noqa: E731
            elif fault == "altered_answer":
                new = lambda self, x: _altered(execute(self, x))  # noqa: E731
            else:
                raise ValueError(f"{fault} cannot happen in a {system} cell")
            stack.enter_context(_patched(plan_mod.Plan, "execute", new))
        else:
            raise ValueError(f"no faults for system {system!r}")
        yield


def run_with_fault(workload: str, fault: str, seed: int, seconds: float, **kw) -> dict:
    """One run of ``workload`` with ``fault`` planted; returns its result."""
    sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src")) if p not in sys.path]
    import run

    config = kw.get("config") or run.resolve(workload, kw.get("spec")).config
    with planted(fault, config["system"]):
        return run.run_cell(workload, seed, seconds, False, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        result = run_with_fault(args.workload, args.fault, seed, args.seconds)
        row = {"fault": args.fault, "seed": seed, "correct": result["correct"],
               "checks": result["checks"], "metrics": result["metrics"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
