#!/usr/bin/env python3
"""The control and the planted faults that the check of an NPB FT cell
must catch.

    python bench/npb_faults.py --workload npb_ft_c_p1 --fault control --seeds 1 2 3

Each fault patches the program under the harness for one run; the run
is otherwise the benchmark's own (``run.run_cell``), and prints the
numbers compared with their limits. The benchmark's runs never import
this file.

``control``
    The float64 reference put in the transforms' place, computed in the
    precision below the configuration's complex64: inputs and answers of
    XLA's ``fftn``/``ifftn`` rounded to bfloat16, in the plan's layouts.
``evolve_skipped``
    Evolve returns the state it is given.
``evolve_not_carried``
    Every iteration evolves the forward transform's output, not the state
    left by the iteration before.
``checksum_strides``
    The checksum sums ``u2[5j, 2j, j]`` (mod the sides), not
    ``u2[5j, 3j, j]``.
``inverse_left_out``
    Every seventh iteration leaves out its inverse transform: its checksum
    and output are those of the iteration before.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import itertools
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
FAULTS = ("control", "evolve_skipped", "evolve_not_carried", "checksum_strides",
          "inverse_left_out")


def _control_transforms(plan):
    """(forward, inverse) in bfloat16 around XLA's FFT, in the plan's
    spectrum layout."""
    import jax
    import jax.numpy as jnp

    from faults import _bf16

    order = [ax.orig + 3 for ax in plan.spectral_axes()]
    back = sorted(range(3), key=order.__getitem__)
    forward = jax.jit(lambda x: _bf16(jnp.transpose(jnp.fft.fftn(_bf16(x)), order)))
    inverse = jax.jit(lambda y: _bf16(jnp.fft.ifftn(jnp.transpose(_bf16(y), back))))
    return forward, inverse


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program for the ``with`` block."""
    from faults import _patched

    from repro.apps import npb_ft
    from repro.core import plan as plan_mod

    NPBFT = npb_ft.NPBFT
    with contextlib.ExitStack() as stack:
        if fault == "control":
            made = {}

            def control(self, inverse, x):
                if id(self) not in made:
                    made[id(self)] = _control_transforms(self)
                return made[id(self)][inverse](x)

            stack.enter_context(_patched(plan_mod.Plan, "execute", functools.partialmethod(
                control, False)))
            stack.enter_context(_patched(plan_mod.Plan, "inverse", functools.partialmethod(
                control, True)))
        elif fault == "evolve_skipped":
            stack.enter_context(_patched(NPBFT, "evolve", lambda self, u_hat: u_hat))
        elif fault == "evolve_not_carried":
            iterate = NPBFT.iterate
            stack.enter_context(_patched(
                NPBFT, "iterate", lambda self, u_hat: (u_hat,) + iterate(self, u_hat)[1:]))
        elif fault == "checksum_strides":
            stack.enter_context(_patched(npb_ft, "checksum_positions", functools.partial(
                npb_ft.checksum_positions, strides=(5, 2, 1))))
        elif fault == "inverse_left_out":
            iterate, calls, last = NPBFT.iterate, itertools.count(), {}

            def left_out(self, u_hat):
                if next(calls) % 7 == 6 and "u2" in last:
                    return self.evolve(u_hat), last["u2"], self.checksum(last["u2"])
                u_hat, last["u2"], c = iterate(self, u_hat)
                return u_hat, last["u2"], c

            stack.enter_context(_patched(NPBFT, "iterate", left_out))
        else:
            raise ValueError(f"no fault {fault!r}; faults: {FAULTS}")
        yield


def run_with_fault(workload: str, fault: str, seed: int, seconds: float, **kw) -> dict:
    """One run of ``workload`` with ``fault`` planted; returns its result."""
    sys.path[:0] = [p for p in (str(BENCH), str(BENCH.parent / "src")) if p not in sys.path]
    import run

    with planted(fault):
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
