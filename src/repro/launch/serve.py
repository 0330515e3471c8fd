"""Serving launcher: batched requests through the Engine on a mediated
dataplane.

``python -m repro.launch.serve [--arch granite-3-2b] [--full]
[--param-dtype bfloat16] [--mode cord|bypass|socket] [--requests 8]
[--prompt-lens 6,7,8,9,10] [--kv-len 128] [--tenants default]
[--block-size 16] [--n-blocks N] [--prefill-chunk 512] [--timeline]``

The engine runs on a :class:`~repro.core.dataplane.Dataplane` of the
chosen mode with emulated OS costs on, over a one-device mesh, so every
sharding edge of the model crosses the mediation pipeline (on a TPU the
Pallas dataplane kernels).  ``--full`` serves the published
configuration (default: the CPU smoke preset); weights are random from
``--seed``, held in ``--param-dtype`` (default: the configuration's).
Request *i* has prompt length ``prompt_lens[i % k]`` and tenant
``tenants[i % m]``; ``--kv-len`` is each request's cache bound (rounded
up to a whole number of blocks).

``--block-size`` switches the continuous engine to the paged KV block
pool (docs/serving.md); ``--n-blocks`` sizes the pool (0 = the stripe
layout's token capacity); ``--prefill-chunk`` bounds how many prompt
tokens one engine tick may prefill (0 disables chunking).

``--timeline`` attaches a :class:`~repro.core.obs.CounterTimeline` to the
engine: one per-tick snapshot of the serve counter block (WFQ grants,
served tokens, slot occupancy, deferrals) plus active-slot / queue-depth
gauges, written to ``runs/<arch>_serve_timeline.json`` with per-tenant
sparkline panels on the console (docs/observability.md).

``--elastic`` (implies ``--timeline``) closes the serve-side control
loop (docs/elasticity.md): a
:class:`~repro.runtime.elastic.ServeElasticController` rides the
engine's ``on_tick`` hook, watching the timeline rate series — by
default ``throttled_pct`` (admission deferrals), since decode traffic is
slot-bound — and on a sustained over-threshold signal shrinks the
per-tenant slot budget (``Engine.set_slot_budget``, enforced by
preemption with exact temp-0 resume) instead of remeshing; the release
arm restores the pre-shrink budget after sustained quiet.  Configure via
``elastic.*`` overrides, e.g. ``elastic.thresholds=throttled_pct=50
elastic.release_thresholds=throttled_pct=10 elastic.sustain=2``.
"""

import argparse
import dataclasses
import os
import time

import jax
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs import apply_overrides, get_model_config
from repro.configs.base import (
    DataplaneConfig,
    ElasticConfig,
    ObsConfig,
    ServeConfig,
)
from repro.core import CounterTimeline, Dataplane
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.layers.common import dtype_of
from repro.models import build_model
from repro.runtime import ServeElasticController
from repro.serve import Engine, Request


def _ints(text: str) -> tuple[int, ...]:
    return tuple(int(v) for v in text.split(",") if v)


def _names(text: str) -> tuple[str, ...]:
    return tuple(v for v in text.split(",") if v)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="the published configuration (default: the CPU "
                         "smoke preset of --arch)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--param-dtype", default=None,
                    help="dtype the weights are held in (default: the "
                         "configuration's param_dtype)")
    ap.add_argument("--mode", default="cord",
                    choices=("bypass", "cord", "socket"))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", type=_ints, default=(6, 7, 8, 9, 10),
                    help="comma list; request i gets prompt_lens[i % k]")
    ap.add_argument("--tenants", type=_names, default=("default",),
                    help="comma list; request i belongs to tenants[i % m]")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--kv-len", type=int, default=128,
                    help="per-request cache bound in tokens (prefill cover "
                         "+ new tokens + 1 must fit)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged KV: pool block size in tokens (0 = legacy "
                         "fixed stripe; 16 is a good starting point)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="paged KV: usable pool blocks (0 = auto: the "
                         "stripe layout's token capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="chunked prefill: tokens per prefill tick "
                         "(power of two >= 8; 0 disables chunking)")
    ap.add_argument("--timeline", action="store_true",
                    help="per-tick engine snapshots into "
                         "runs/<arch>_serve_timeline.json")
    ap.add_argument("--elastic", action="store_true",
                    help="watch the serve timeline and move the per-tenant "
                         "slot budget down/up on sustained threshold "
                         "crossings (implies --timeline; docs/elasticity.md)")
    ap.add_argument("overrides", nargs="*", default=[],
                    help="elastic.* key=value overrides")
    return ap


def load_model(args, device):
    """``(cfg, model, params)`` with seeded random weights on ``device``,
    held in ``args.param_dtype``.  The layers cast every weight to the
    activation dtype at use, so weights held at that dtype give the same
    logits in half the memory of float32."""
    cfg = get_model_config(args.arch, smoke=not args.full)
    if args.param_dtype:
        cfg = dataclasses.replace(cfg, param_dtype=args.param_dtype)
    model = build_model(cfg)
    dt = dtype_of(cfg.param_dtype)
    init = jax.jit(lambda key: jax.tree.map(lambda a: a.astype(dt),
                                            model.init(key)),
                   out_shardings=SingleDeviceSharding(device))
    return cfg, model, init(jax.random.PRNGKey(args.seed))


def build_engine(cfg, model, params, args, devices, *, obs=None,
                 obs_every: int = 1) -> Engine:
    """An Engine whose dataplane (``args.mode``, emulated OS costs on)
    spans exactly ``devices``."""
    dp = Dataplane(DataplaneConfig(mode=args.mode, emulate_costs=True),
                   mesh=make_local_mesh(devices), tenant=args.tenants[0],
                   tenants=args.tenants)
    kv_len = args.kv_len
    if args.block_size > 0:              # keep block_size | kv_cache_len
        kv_len = -(-kv_len // args.block_size) * args.block_size
    return Engine(model, params, cfg,
                  ServeConfig(max_batch=args.max_batch,
                              max_new_tokens=args.max_new_tokens,
                              kv_cache_len=kv_len,
                              block_size=args.block_size,
                              n_blocks=args.n_blocks,
                              prefill_chunk=args.prefill_chunk),
                  dp=dp, eos_id=-1, obs=obs, obs_every=obs_every)


def make_requests(cfg, args, *, keep_logits: bool = False) -> list[Request]:
    """``args.requests`` seeded prompts over the length and tenant lists;
    ``keep_logits`` records each emitted token's logits row."""
    rng = np.random.default_rng(args.seed)
    lens, tenants = args.prompt_lens, args.tenants
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, lens[i % len(lens)],
                                        dtype=np.int32),
                    max_new_tokens=args.max_new_tokens,
                    tenant=tenants[i % len(tenants)],
                    logits=[] if keep_logits else None)
            for i in range(args.requests)]


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()[:1]
    cfg, model, params = load_model(args, devices[0])
    # serve-appropriate elastic defaults: deferral share is the decode
    # pressure signal (denied never moves on the serve counter block)
    elastic = apply_overrides(
        ElasticConfig(enabled=args.elastic,
                      thresholds=("throttled_pct=50",),
                      release_thresholds=("throttled_pct=10",)),
        [o[len("elastic."):] for o in args.overrides
         if o.startswith("elastic.")])
    obs = ObsConfig(timeline=args.timeline or elastic.enabled)
    timeline = CounterTimeline(source=f"serve/{args.arch}") \
        if obs.timeline else None
    eng = build_engine(cfg, model, params, args, devices, obs=timeline,
                       obs_every=obs.every)
    controller = None
    if elastic.enabled:
        controller = ServeElasticController(elastic, timeline, eng)
        eng.on_tick = controller.tick
    reqs = make_requests(cfg, args)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    ttft = [r.t_first - t0 for r in done if r.t_first is not None]
    print(f"served {len(done)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s, "
          f"{args.mode} dataplane on {jax.devices()[0].platform}, "
          f"{eng.decode_compile_count()} decode compiles, "
          f"mean TTFT {1e3*sum(ttft)/max(len(ttft),1):.0f} ms)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")
    for tenant, stats in eng.tenant_report().items():
        print(f"  tenant {tenant}: {stats}")
    if controller is not None:
        print(f"elastic: {controller.shrinks} budget shrinks, "
              f"{controller.grows} grow-backs "
              f"(slot budget now {eng.slot_budget()})")
    if timeline is not None:
        path = timeline.save(os.path.join(
            obs.out_dir, f"{args.arch}_serve_timeline.json"))
        print(f"timeline artifact: {path} "
              f"({len(timeline.samples)} ticks, "
              f"{len(timeline.events)} events)")
        for ev in timeline.events:
            print(f"  event step {ev['step']:4d} {ev['kind']:8s} "
                  f"{ev['tenant']}: {ev['detail']}")
        if obs.panel:
            print(timeline.panel(width=obs.spark_width))


if __name__ == "__main__":
    main()
