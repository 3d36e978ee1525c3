"""Closed loop: ``clients`` callers, each sending its next request once
the answer to its last one is in. A request is due when it is sent.

The window closes at the first answer after ``seconds``; requests still
out then are waited for and not counted.
"""

from __future__ import annotations

import time
from collections import deque


def run(spec: dict, seed: int, seconds: float, submit, complete) -> tuple:
    clients = int(spec["clients"])
    if clients < 1:
        raise ValueError(f"a closed loop needs clients >= 1, not {clients}")
    out, done = deque(), 0
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        while len(out) < clients:
            out.append(submit())
        complete(out.popleft())
        done += 1
    window_s = time.perf_counter() - start
    while out:
        complete(out.popleft())
    return done, window_s
