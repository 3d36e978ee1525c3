"""End-to-end training driver example: train a ~100M-param qwen-family
model for a few hundred steps on synthetic data, with checkpointing and
failure recovery.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

(CPU note: ~100M params on one core is slow; --tiny uses the reduced
config so the example completes in ~a minute. The full invocation is the
same code path the cluster launcher uses.)
"""

import argparse
import dataclasses
import sys

import jax
import numpy as np

sys.path.insert(0, "src")

from repro.configs import TrainConfig, get_config
from repro.core.compat import use_compile_cache
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.models import Model
from repro.runtime import StepMonitor, run_with_recovery
from repro.train import init_train_state, make_train_step


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_example_ckpt")
    args = ap.parse_args()

    if args.tiny:
        cfg = get_config("qwen2.5-32b", reduced=True)
        batch, seq = 8, 64
    else:
        # ~100M params: 12L x 640d, qwen-family
        cfg = dataclasses.replace(
            get_config("qwen2.5-32b"),
            num_layers=12, d_model=640, num_heads=10, num_kv_heads=2,
            d_ff=1728, vocab_size=32064,
        )
        batch, seq = 8, 256
    n = cfg.param_count()
    print(f"arch={cfg.name} params~{n/1e6:.1f}M batch={batch} seq={seq}")

    model = Model(cfg)
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=20, total_steps=args.steps)
    ds = SyntheticLM(DataConfig(cfg.vocab_size, seq, batch, seed=0, noise=0.02))
    ckpt = CheckpointManager(args.ckpt, keep=2)
    mon = StepMonitor()

    def loop(resume):
        state, _ = init_train_state(model, jax.random.PRNGKey(0), tcfg)
        start = ckpt.latest_step() or 0
        if start:
            state = ckpt.restore(start, state)
            print(f"resumed at step {start}")
        step_fn = jax.jit(make_train_step(model, tcfg, None), donate_argnums=(0,))
        import jax.numpy as jnp

        for s in range(start, args.steps):
            b = {k: jnp.asarray(v) for k, v in ds.batch_at(s).items()}
            mon.start()
            state, m = step_fn(state, b)
            st = mon.stop(tokens=batch * seq)
            if s % 20 == 0 or s == args.steps - 1:
                print(f"step {s:4d} loss {float(m['loss']):.4f} "
                      f"lr {float(m['lr']):.2e} {mon.tokens_per_sec:.0f} tok/s")
            if (s + 1) % 100 == 0:
                ckpt.save(s + 1, state)
        ckpt.wait()

    run_with_recovery(loop, max_restarts=1)
    print("straggler report:", mon.straggler_report())


if __name__ == "__main__":
    main()
