"""Training driver: a small LM for a few hundred steps with the full
substrate — sharded-ready train step, AdamW, cosine schedule, atomic
checkpoints, and a mid-run injected failure that the restart policy
recovers from (the fault-tolerance path the cluster deployment relies on).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""

import sys

sys.path.insert(0, "src")

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.data.synth import token_pipeline
from repro.ft import FailureInjector, RestartPolicy, run_with_restarts
from repro.launch import steps as step_lib
from repro.models import transformer as T
from repro.optim import adamw_init


def main(num_steps=200, arch="musicgen-large", ckpt_dir="/tmp/train_lm_ck"):
    cfg = configs.get_config(arch, "smoke")
    train_step = jax.jit(step_lib.make_train_step(
        cfg, peak_lr=3e-3, warmup=20, total=num_steps))

    def init_state():
        params = T.init_params(cfg, jax.random.key(0))
        return {"params": params, "opt": adamw_init(params)}

    losses = []

    def step_fn(state, step):
        tokens, labels = next(token_pipeline(
            8, 32, cfg.vocab_size, seed=1, start_step=step))
        params, opt, metrics = train_step(
            state["params"], state["opt"],
            {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)})
        if step % 20 == 0 or step == num_steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        losses.append(float(metrics["loss"]))
        return {"params": params, "opt": opt}

    t0 = time.monotonic()
    out = run_with_restarts(
        policy=RestartPolicy(ckpt_dir=ckpt_dir, ckpt_every=50,
                             max_restarts=3),
        init_state=init_state, step_fn=step_fn, num_steps=num_steps,
        injector=FailureInjector(fail_at=[num_steps // 2]),
        meta_fn=lambda step: {"data_cursor": step})
    dt = time.monotonic() - t0

    print(f"\ndone in {dt:.1f}s; survived {out['restarts']} injected "
          f"failure(s), resumed from steps {out['resumed_from']}")
    first, last = losses[0], sum(losses[-10:]) / 10
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'LEARNING' if last < first * 0.9 else 'check config'})")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="musicgen-large")
    args = ap.parse_args()
    main(num_steps=args.steps, arch=args.arch)
