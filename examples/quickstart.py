"""Quickstart: build a model, train it through the CoRD dataplane for a few
steps on all local devices, and inspect what the dataplane saw.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs import get_model_config
from repro.configs.base import DataplaneConfig, RunConfig, TrainConfig
from repro.core import Dataplane
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.train import init_state, make_explicit_dp_step


def main():
    cfg = get_model_config("gemma3-1b", smoke=True)
    model = build_model(cfg)
    mesh = make_local_mesh(jax.devices())

    # The paper's knob: route every dataplane op through the mediation
    # layer ("cord"), raw kernel-bypass ("bypass"), or the socket path.
    dp = Dataplane(DataplaneConfig(mode="cord"), mesh=mesh)

    run = RunConfig(train=TrainConfig(steps=20, learning_rate=5e-3,
                                      warmup_steps=5))
    step = make_explicit_dp_step(model, run, dp, axis="data")
    state = init_state(model, jax.random.PRNGKey(0))
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                global_batch=16))

    for i in range(20):
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
        state, metrics = step(state, batch)
        if i % 5 == 0:
            print(f"step {i:3d}  loss {float(metrics['loss']):.4f}")

    print("\nWhat the OS saw on the dataplane (telemetry policy):")
    print(dp.telemetry.report())


if __name__ == "__main__":
    main()
