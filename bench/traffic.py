"""The load generator: a traffic file and a seed become requests.

A traffic file (``bench/traffic/<name>.json``) holds parameters only.
Its ``arrival`` names the arrival process, a module of its own,
``bench/arrivals/<arrival>.py``, found by that name, with

    run(spec, seed, seconds, submit, complete) -> (completed, window_s)

It offers requests to a driver for ``seconds``: ``submit()`` sends one
and returns its handle, ``complete(handle)`` waits for its answer. It
returns the requests completed inside the window and the window's
length. A new arrival process, or new parameters of one, is a new file,
never an edit to this one or to a driver.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

ARRIVALS = Path(__file__).resolve().parent / "arrivals"


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of ``seed`` (any whole number)."""
    return np.random.default_rng([seed % (1 << 64), stream])


class Traffic:
    def __init__(self, spec: dict, seed: int, seconds: float):
        path = ARRIVALS / f"{spec['arrival']}.py"
        if not path.is_file():
            raise ValueError(f"unknown arrival {spec['arrival']!r}: no {path.name} in bench/arrivals")
        module_spec = importlib.util.spec_from_file_location(f"arrival_{path.stem}", path)
        self.arrival = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(self.arrival)
        self.spec = spec
        self.seed = seed
        self.seconds = seconds

    def run(self, submit, complete) -> tuple:
        """(requests completed inside the window, window seconds)."""
        return self.arrival.run(self.spec, self.seed, self.seconds, submit, complete)
