"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
through the CoRD dataplane, with checkpointing, fault tolerance and int8
gradient compression.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import os
import shutil

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AttentionConfig, DataplaneConfig, ModelConfig, RunConfig, TrainConfig,
)
from repro.core import Dataplane
from repro.data import DataConfig, ShardedLoader, SyntheticLM
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.runtime import FaultInjector, run_loop
from repro.train import init_state, make_explicit_dp_step

# ~100M params: 12L, d_model 512, vocab 50k (llama-style)
CFG_100M = ModelConfig(
    name="lm-100m", family="dense", num_layers=12, d_model=512, d_ff=2048,
    vocab_size=50_304,
    attention=AttentionConfig(num_heads=8, num_kv_heads=4),
    max_seq_len=1024, dtype="float32",
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--mode", default="cord")
    ap.add_argument("--inject-failure", action="store_true")
    args = ap.parse_args()

    model = build_model(CFG_100M)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))))
    print(f"model: {n/1e6:.1f}M params")

    mesh = make_local_mesh(jax.devices())
    dp = Dataplane(DataplaneConfig(mode=args.mode), mesh=mesh)
    run = RunConfig(train=TrainConfig(
        steps=args.steps, learning_rate=3e-3, warmup_steps=30,
        grad_compression="int8", checkpoint_every=50,
        checkpoint_dir="/tmp/repro_train_lm"))
    shutil.rmtree("/tmp/repro_train_lm", ignore_errors=True)

    step = make_explicit_dp_step(model, run, dp, axis="data")
    state = init_state(model, jax.random.PRNGKey(0), compression="int8")
    ds = SyntheticLM(DataConfig(vocab_size=CFG_100M.vocab_size,
                                seq_len=args.seq_len,
                                global_batch=args.batch))
    loader = ShardedLoader(ds)

    def wrap(s, b):
        return step(s, {k: jnp.asarray(v) for k, v in b.items()})

    injector = FaultInjector(fail_steps=(args.steps // 2,)) \
        if args.inject_failure else None
    state, report = run_loop(
        wrap, state, loader, steps=args.steps,
        ckpt_dir="/tmp/repro_train_lm", checkpoint_every=50,
        injector=injector, log_every=20)

    first = report.metrics[0]["loss"]
    last = report.metrics[-1]["loss"]
    print(f"\nloss: {first:.3f} -> {last:.3f} over {report.steps_run} steps "
          f"({report.failures} failures, {report.restores} restores)")
    print(dp.telemetry.report())


if __name__ == "__main__":
    main()
