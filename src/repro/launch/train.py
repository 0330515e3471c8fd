"""Training launcher: ``python -m repro.launch.train --arch gemma3-1b
[--mode cord] [--timeline] [--elastic] [key=value overrides...]``

Runs the explicit-DP trainer on the local CPU mesh (all host devices) with
the fault-tolerant runtime; production meshes use the same RunConfig with
make_production_mesh on real hardware.

``--timeline`` switches the step to ``runtime_accounting=True`` (the
per-tenant runtime-state pytree threaded through the gradient sync) and
snapshots ``dp.runtime_report`` into a
:class:`~repro.core.obs.CounterTimeline` after each step — host-side
reads between steps only, so traced results are bit-identical to a run
without the flag (tests/test_obs.py).  The run writes the
schema-versioned artifact ``runs/<arch>_timeline.json`` and prints
per-tenant sparkline panels (docs/observability.md).
``--timeline-sink PATH`` additionally streams every snapshot/event to a
JSONL file as the run progresses; ``--timeline-rotate BYTES`` seals the
sink into ``PATH.1..N`` segments once each passes the size budget, so a
long run never grows one unbounded file
(``CounterTimeline.read_rotated`` stitches the segments back together —
docs/observability.md).

``--elastic`` (implies ``--timeline``) closes the control loop
(docs/elasticity.md): an :class:`~repro.runtime.elastic.ElasticController`
watches the timeline's rate series against ``ElasticConfig`` thresholds
with hysteresis, and on a sustained over-threshold signal remeshes the
live TrainState onto a shrunken mesh slice mid-run, rebuilding the
dataplane and the jitted step against the new mesh and recording
``trigger``/``remesh`` events into the timeline artifact.  Configure via
``elastic.*`` overrides, e.g. ``elastic.thresholds=denied_pct=50
elastic.sustain=3 elastic.meter_quota_bytes=1000000``.
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import apply_overrides, get_model_config
from repro.configs.base import (
    DataplaneConfig,
    ElasticConfig,
    ObsConfig,
    RunConfig,
    TrainConfig,
)
from repro.core import CounterTimeline, Dataplane
from repro.core.policies import QuotaPolicy, TelemetryPolicy
from repro.data import DataConfig, ShardedLoader, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.runtime import ElasticController, run_loop
from repro.train import init_state, make_explicit_dp_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--mode", default="cord",
                    choices=["bypass", "cord", "socket"])
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--timeline", action="store_true",
                    help="thread per-tenant runtime accounting through the "
                         "step and write runs/<arch>_timeline.json")
    ap.add_argument("--timeline-sink", default=None, metavar="PATH",
                    help="stream timeline snapshots/events to a JSONL file "
                         "as the run progresses (docs/observability.md)")
    ap.add_argument("--timeline-rotate", type=int, default=0,
                    metavar="BYTES",
                    help="rotate the JSONL sink into PATH.1..N segments "
                         "once each passes this many bytes (0 = never)")
    ap.add_argument("--elastic", action="store_true",
                    help="watch the timeline rate series and remesh onto a "
                         "shrunken mesh slice on sustained over-threshold "
                         "windows (implies --timeline; docs/elasticity.md)")
    ap.add_argument("overrides", nargs="*", default=[])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_model_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    train = TrainConfig()
    train = apply_overrides(train, [o for o in args.overrides
                                    if not o.startswith(("model.",
                                                         "elastic."))])
    elastic = apply_overrides(
        ElasticConfig(enabled=args.elastic),
        [o[len("elastic."):] for o in args.overrides
         if o.startswith("elastic.")])
    obs = ObsConfig(timeline=args.timeline or elastic.enabled
                    or bool(args.timeline_sink))
    run = RunConfig(train=train, obs=obs, elastic=elastic)

    mesh = make_local_mesh(jax.devices())
    policies = None
    if elastic.enabled and elastic.meter_quota_bytes:
        # observe-only metering: runtime traffic over the budget marks the
        # tenant's `denied` counter — the watcher's default trigger signal
        policies = [TelemetryPolicy(),
                    QuotaPolicy(hard=False,
                                limits={"default": elastic.meter_quota_bytes})]

    ctx = {"dp": Dataplane(DataplaneConfig(mode=args.mode), mesh=mesh,
                           policies=policies)}
    ctx["step"] = make_explicit_dp_step(model, run, ctx["dp"], axis="data",
                                        runtime_accounting=obs.timeline)
    state = init_state(model, jax.random.PRNGKey(train.seed),
                       compression=train.grad_compression)
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                seq_len=train.seq_len,
                                global_batch=train.global_batch,
                                seed=train.seed))
    loader = ShardedLoader(ds)

    timeline = CounterTimeline(source=f"train/{args.arch}",
                               sink=args.timeline_sink,
                               rotate_bytes=args.timeline_rotate
                               if args.timeline_sink else 0) \
        if obs.timeline else None
    controller = ElasticController(elastic, timeline, mesh) \
        if elastic.enabled else None
    rt = {"state": ctx["dp"].runtime_init(), "step": 0} \
        if obs.timeline else None

    def rebuild(new_mesh) -> None:
        """Recompile the dataplane + step against the shrunken mesh,
        keeping the policy objects (cumulative trace-time metering)."""
        ctx["dp"] = Dataplane(DataplaneConfig(mode=args.mode), mesh=new_mesh,
                              policies=ctx["dp"].policies)
        ctx["step"] = make_explicit_dp_step(model, run, ctx["dp"],
                                            axis="data",
                                            runtime_accounting=True)

    def wrap(s, b):
        b = {k: jnp.asarray(v) for k, v in b.items()}
        if rt is None:
            return ctx["step"](s, b)
        s, metrics, rt["state"] = ctx["step"](s, b, rt["state"])
        rt["step"] += 1
        if timeline is not None and rt["step"] % obs.every == 0:
            # host-side read of the accumulated counter block, strictly
            # between steps — the traced computation never sees the obs
            gauges = controller.watcher.gauges() if controller else None
            timeline.snapshot(rt["step"],
                              ctx["dp"].runtime_report(rt["state"]),
                              gauges=gauges)
            if controller is not None:
                s, moved = controller.drive(s, rt["step"])
                if moved:
                    rebuild(controller.mesh)
                    # runtime counters survive the move as host arrays
                    rt["state"] = jax.tree.map(
                        lambda x: np.asarray(x),
                        jax.device_get(rt["state"]))
                    print(f"[elastic] remeshed onto "
                          f"{controller.mesh.devices.shape} at step "
                          f"{rt['step']}")
        return s, metrics

    state, report = run_loop(
        wrap, state, loader, steps=train.steps,
        ckpt_dir=train.checkpoint_dir if train.checkpoint_every else None,
        checkpoint_every=train.checkpoint_every,
        async_ckpt=train.async_checkpoint, log_every=train.log_every)
    print(f"done: {report.steps_run} steps, "
          f"final loss {report.metrics[-1]['loss']:.4f}")
    print(ctx["dp"].telemetry.report())
    if timeline is not None:
        path = timeline.save(os.path.join(obs.out_dir,
                                          f"{args.arch}_timeline.json"))
        timeline.close()
        print(f"timeline artifact: {path} ({len(timeline.samples)} samples, "
              f"{len(timeline.events)} events)")
        for ev in timeline.events:
            print(f"  event step {ev['step']:4d} {ev['kind']:8s} "
                  f"{ev['tenant']}: {ev['detail']}")
        if obs.panel:
            print(timeline.panel(width=obs.spark_width))


if __name__ == "__main__":
    main()
