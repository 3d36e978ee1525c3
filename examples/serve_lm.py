"""Batched serving example: slot-based continuous batching with ragged
prompts on a reduced model.

    PYTHONPATH=src python examples/serve_lm.py
"""

import sys
import time

import numpy as np

sys.path.insert(0, "src")

import jax

from repro.configs import ServeConfig, get_config
from repro.core.compat import use_compile_cache
from repro.models import Model
from repro.serve import ServeEngine


def main():
    use_compile_cache()
    cfg = get_config("gemma2-9b", reduced=True)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(max_batch=4, max_seq=96))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
        for n in rng.integers(4, 24, size=10)
    ]
    t0 = time.perf_counter()
    results = engine.run(prompts, max_new=24)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in results.values())
    print(f"served {len(results)} ragged requests / {total} tokens in {dt:.1f}s "
          f"({total/dt:.1f} tok/s on CPU)")
    for uid in sorted(results)[:3]:
        print(f"  req {uid} -> {results[uid][:10]}...")


if __name__ == "__main__":
    main()
