#!/usr/bin/env python3
"""Chip smoke test: the system's main path, once, on a TPU.

    python chip_smoke.py [--out DIR]       # one chip: serve granite-3-2b
    python chip_smoke.py --chips 4         # four chips: the verbs paths

One chip (the default): granite-3-2b at its published widths (40 layers,
d_model 2048, seeded random weights) is served through the normal serve
entry point (``repro.launch.serve``): the continuous, paged engine
(block size 16, chunked prefill) on a ``mode="cord"`` dataplane with
emulated OS costs, whose every sharding edge runs the Pallas dataplane
kernels.  Eight requests from two tenants, prompts of 77–1024 tokens,
32 new tokens each at temperature 0.  Checked: every request finishes
with its token count, all logits are finite, decode compiled once, the
compiled decode step holds the kernels (``tpu_custom_call``), a mediated
collective lands in-kernel cost in the tenant's ``kernel_iters``
counter, the served logits agree with a cache-free float32 forward
(``LOGIT_TOL_MAX``, ``LOGIT_TOL_MEAN``), and a ``mode="bypass"``
dataplane gives the same greedy tokens.

Four chips (``--chips 4``, nothing else runs): RC ``windowed_send``
between two chips in bypass and cord, ``conn_send`` under a seeded
``WireFault`` against the lossless run, a connection-table migration
from chips (0, 1) to chips (2, 3) partway through a transfer, and a
mediated ``psum`` over all four chips against bypass.  Every mesh is
built from an explicit device list.

Times printed are smoke numbers, not benchmark metrics.  The last line
of standard output is ``{"ok": true, "device": {...}}``; any failed
check raises, and without a TPU the script exits non-zero before doing
anything.  Artifacts go to ``--out`` (default ``smoke_out/``).
"""

import argparse
import json
import os
import sys
import time
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))

# Prompt lengths fall in two prefill shapes (one 128-token bucket, one
# two-chunk 1024 cover), so the run compiles few whole-model programs.
# Weights are held in bfloat16: the layers cast each weight to the
# bfloat16 activation dtype at use, so the logits are those of float32
# weights, and float32 weights (10.1 GB) plus the decode step's bfloat16
# weight copies and KV buffers need 19.7 GiB of the chip's 15.75 GiB.
SERVE_ARGV = [
    "--full", "--arch", "granite-3-2b", "--seed", "0", "--mode", "cord",
    "--param-dtype", "bfloat16",
    "--requests", "8", "--tenants", "alice,bob",
    "--prompt-lens", "100,1000,128,600,77,1024,120,777",
    "--max-new-tokens", "32", "--max-batch", "4",
    "--block-size", "16", "--n-blocks", "256", "--kv-len", "4096",
    "--prefill-chunk", "512",
]

# Served logits (bf16 activations, the configuration's precision)
# against a cache-free float32 forward at "highest" matmul precision,
# over every emitted token's row.  The served residual stream is rounded
# to bf16 (unit roundoff u = 2**-8) after each of the 40 layers' two
# sublayers; 80 independent roundings of mean size u/2 accumulate like a
# random walk to about sqrt(80) * 2**-9 = 1.7e-2 of a typical logit.
# Both errors — the largest |served - ref| over the largest |ref|, and
# the mean |served - ref| over the mean |ref| — must stay within 2**-5,
# about twice that.  fp8 arithmetic (u = 2**-4) would exceed it several
# times over, and a wrong cache position or a value-altering mediation
# stage moves logits by the order of their own scale.
LOGIT_TOL_MAX = 2.0 ** -5
LOGIT_TOL_MEAN = 2.0 ** -5


def _check(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def platform_gate(chips: int):
    """Print versions and devices; exit non-zero unless JAX found at
    least ``chips`` TPU devices.  There is no CPU fallback."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"chip_smoke: the repository's src/repro is not beside "
                 f"{__file__}; run it from a checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu}")
    devs = jax.devices()
    for d in devs:
        print(f"  device {d.id}: {d.platform} {d.device_kind}")
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); this check runs only on the chip")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# one chip: serve granite-3-2b through the mediated dataplane
# ---------------------------------------------------------------------------

def _reference_logits(params, cfg, tokens, at):
    """Cache-free float32 forward of ``tokens`` (R, T), naive attention,
    no dataplane: the logits at positions ``at`` (R, K).  It runs one
    layer per call, so only that layer's weights are ever upcast."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.layers.common import rmsnorm
    from repro.layers.embedding import embed, logits
    from repro.models.transformer import _layer, layer_flags

    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    window, theta = layer_flags(ref_cfg)

    @jax.jit
    def layer(lp, x, w, th):
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        return _layer(lp, x, cfg=ref_cfg, dp=None, positions=pos, window=w,
                      theta=th, mode="train", impl="naive")[0]

    @jax.jit
    def head(params, x, at):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return logits(params["embed"], x)

    x = jax.jit(lambda p, t: embed(p, t, jnp.float32))(params["embed"],
                                                        tokens)
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = layer(lp, x, window[i], theta[i])
    return head(params, x, at)


def _logit_errors(done, params, cfg):
    """(max |served - ref| / max |ref|, mean |served - ref| / mean |ref|)
    over every emitted token's logits row of every request."""
    import jax
    import numpy as np

    n_new = len(done[0].out_tokens)
    t_ref = max(len(r.prompt) for r in done) + n_new
    tokens = np.zeros((len(done), -(-t_ref // 128) * 128), np.int32)
    at = np.zeros((len(done), n_new), np.int32)
    for j, r in enumerate(done):
        fed = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1])])
        tokens[j, :len(fed)] = fed                  # right pad: causal-safe
        at[j] = len(r.prompt) - 1 + np.arange(n_new)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_reference_logits(params, cfg, tokens, at))
    got = np.stack([np.stack(r.logits) for r in done])
    diff = np.abs(got - want)
    return (float(diff.max() / np.abs(want).max()),
            float(diff.mean() / np.abs(want).mean()))


def _kernel_iters(dp, device) -> float:
    """One mediated collective with runtime state threaded: the in-kernel
    cost the dataplane kernels report for tenant ``bob``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import compat

    def body(v, rt):
        return dp.psum(v, "data", state=rt, tenant="bob")

    f = jax.jit(compat.shard_map(body, mesh=dp.mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P())))
    x = jax.device_put(jnp.arange(4 * 2048, dtype=jnp.float32), device)
    out, rt = f(x, dp.runtime_init())
    _check(np.array_equal(np.asarray(out), np.asarray(x)),
           "mediated one-chip psum changed its payload")
    return float(dp.runtime_report(rt)["bob"]["kernel_iters"])


def _step_text(eng, args) -> str:
    """Compiled text of the engine's paged decode step at its run shapes."""
    import jax.numpy as jnp
    import numpy as np

    from repro.layers.kvcache import kv_pool_init

    layers, kvh, hd, dt = eng._pool_geom
    pool = kv_pool_init(layers, eng._n_usable, args.block_size, kvh, hd,
                        dtype=dt)
    b = args.max_batch
    return eng._step_pool.lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), pool,
        jnp.asarray(np.zeros((b, eng._tables_len), np.int32)),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool)).compile().as_text()


def serve_phase(device, out_dir: str, argv=SERVE_ARGV) -> dict:
    import jax
    import numpy as np

    from repro.launch import serve as launcher

    args = launcher.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    cfg, model, params = launcher.load_model(args, device)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {n_params / 1e9:.3f} B parameters "
          f"({time.perf_counter() - t0:.1f} s to build)")

    eng = launcher.build_engine(cfg, model, params, args, [device])
    _check(eng.dp.pipeline.pallas,
           "the cord dataplane did not select the Pallas kernels")
    t0 = time.perf_counter()
    done = eng.run(launcher.make_requests(cfg, args, keep_logits=True))
    cold = time.perf_counter() - t0
    _check(len(done) == args.requests, f"{len(done)} of {args.requests} "
           f"requests finished")
    for r in done:
        _check(len(r.out_tokens) == args.max_new_tokens,
               f"request {r.rid} emitted {len(r.out_tokens)} tokens")
        _check(all(np.isfinite(row).all() for row in r.logits),
               f"request {r.rid} has non-finite logits")
    _check(eng.decode_compile_count() == 1,
           f"decode compiled {eng.decode_compile_count()} times")

    t0 = time.perf_counter()
    warm_done = eng.run(launcher.make_requests(cfg, args))
    warm = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in warm_done)
    _check(sorted((r.rid, r.out_tokens) for r in warm_done)
           == sorted((r.rid, r.out_tokens) for r in done),
           "the warm run emitted different tokens")
    print(f"smoke numbers, not benchmark metrics: cold run {cold:.1f} s "
          f"(compiles included), warm run {warm:.2f} s, "
          f"{toks / warm:.1f} tok/s over {toks} tokens")

    text = _step_text(eng, args)
    _check("tpu_custom_call" in text,
           "the compiled mediated decode step holds no Pallas kernel")
    kiters = _kernel_iters(eng.dp, device)
    _check(kiters > 0, "the tenant's kernel_iters counter stayed at 0")
    print(f"compiled decode step: {text.count('tpu_custom_call')} "
          f"tpu_custom_call sites; tenant bob kernel_iters {kiters:.0f}")

    err_max, err_mean = _logit_errors(done, params, cfg)
    print(f"served vs cache-free float32 forward: max error "
          f"{err_max:.3e} of the largest logit (limit {LOGIT_TOL_MAX:.3e}), "
          f"mean error {err_mean:.3e} of the mean logit "
          f"(limit {LOGIT_TOL_MEAN:.3e})")
    _check(err_max <= LOGIT_TOL_MAX and err_mean <= LOGIT_TOL_MEAN,
           "served logits disagree with the cache-free forward")

    bargs = launcher.build_parser().parse_args(argv + ["--mode", "bypass"])
    beng = launcher.build_engine(cfg, model, params, bargs, [device])
    bdone = beng.run(launcher.make_requests(cfg, bargs, keep_logits=True))
    by_rid = {r.rid: r for r in bdone}
    diff = max(float(np.abs(np.stack(r.logits)
                            - np.stack(by_rid[r.rid].logits)).max())
               for r in done)
    scale = max(float(np.abs(np.stack(r.logits)).max()) for r in done)
    print(f"cord vs bypass: largest logit difference {diff:.6g} "
          f"({diff / scale:.3e} of the largest logit {scale:.6g})")
    _check(all(r.out_tokens == by_rid[r.rid].out_tokens for r in done),
           "cord and bypass greedy tokens differ")

    result = {"model": cfg.name, "layers": cfg.num_layers,
              "requests": len(done), "tokens_per_request":
              args.max_new_tokens, "decode_compiles":
              eng.decode_compile_count(), "cold_s": cold, "warm_s": warm,
              "warm_tok_s": toks / warm, "logit_err_max": err_max,
              "logit_err_mean": err_mean, "cord_vs_bypass_max_diff": diff,
              "largest_logit": scale,
              "kernel_iters_bob": kiters,
              "tokens": {r.rid: r.out_tokens for r in done}}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "serve_phase.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


# ---------------------------------------------------------------------------
# four chips: the verbs paths that exist only across chips
# ---------------------------------------------------------------------------

def _pair(devices):
    from repro.core import compat
    return compat.make_mesh((2,), ("rank",), devices=list(devices))


def _dp(mesh, mode: str):
    from repro.configs.base import DataplaneConfig
    from repro.core import Dataplane
    return Dataplane(DataplaneConfig(mode=mode, emulate_costs=True),
                     mesh=mesh)


def _windowed(mesh, dp, cfg, payload):
    """RC send of ``payload`` (n, bytes) from rank 0 to rank 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import compat, verbs

    n = payload.shape[0]

    def body(m):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(cfg)
        qp, _ = verbs.post_recv(dp, cfg, qp, rank, dst=1, n=n)
        out, _, _ = verbs.windowed_send(dp, cfg, qp, m[0], rank, src=0,
                                        dst=1)
        return out[None]

    f = jax.jit(compat.shard_map(body, mesh=mesh,
                                 in_specs=P("rank", None, None),
                                 out_specs=P("rank", None, None)))
    msgs = jnp.asarray(np.stack([payload, np.zeros_like(payload)]))
    return np.asarray(f(msgs))[1]


def _conn_parts(mesh, dp, cfg, q: int, *, fault=None, credits: int = 0):
    """Jitted init / transfer / quiesce pieces of a connection table."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import compat, verbs

    cspec = verbs.conn_specs()

    def init_body():
        rank = jax.lax.axis_index("rank")
        conn = verbs.conn_init(cfg, q)
        if credits:
            conn, _ = verbs.srq_post(dp, cfg, conn, rank, dst=1, n=credits)
        return conn

    def xfer_body(m, conn):
        rank = jax.lax.axis_index("rank")
        out, conn, _ = verbs.conn_send(dp, cfg, conn, m[0], rank, src=0,
                                       dst=1, fault=fault)
        return out[None], conn

    def quiesce_body(conn):
        rank = jax.lax.axis_index("rank")
        conn, _ = verbs.conn_quiesce(dp, cfg, conn, rank, src=0)
        return conn

    spec4 = P("rank", None, None, None)
    return {
        "init": jax.jit(compat.shard_map(init_body, mesh=mesh, in_specs=(),
                                         out_specs=cspec)),
        "xfer": jax.jit(compat.shard_map(xfer_body, mesh=mesh,
                                         in_specs=(spec4, cspec),
                                         out_specs=(spec4, cspec))),
        "quiesce": jax.jit(compat.shard_map(quiesce_body, mesh=mesh,
                                            in_specs=(cspec,),
                                            out_specs=cspec)),
    }


def four_chip_phase(devices) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import compat, verbs
    from repro.runtime.fault import WireFault

    devices = list(devices)
    _check(len(devices) == 4, f"the four-chip phase got {len(devices)} "
           f"devices")
    rng = np.random.default_rng(0)
    ab, cd = _pair(devices[0:2]), _pair(devices[2:4])
    result = {}

    # RC windowed_send chip 0 -> chip 1, bypass and cord
    cfg = verbs.QPConfig(transport="RC", msg_bytes=4096, depth=16,
                         max_outstanding=8)
    payload = rng.integers(0, 256, (64, cfg.msg_bytes), dtype=np.uint8)
    got = {mode: _windowed(ab, _dp(ab, mode), cfg, payload)
           for mode in ("bypass", "cord")}
    for mode, out in got.items():
        _check(np.array_equal(out, payload),
               f"windowed_send ({mode}) delivery differs from the source")
    _check(np.array_equal(got["bypass"], got["cord"]),
           "windowed_send cord and bypass deliveries differ")
    result["windowed_send_bytes"] = int(payload.size)
    print(f"windowed_send RC chip 0 -> 1: {payload.size} bytes delivered "
          f"bit-identically in bypass and cord")

    # conn_send: seeded wire loss against the lossless run
    ccfg = verbs.QPConfig(msg_bytes=1024, depth=8, max_outstanding=3,
                          retry_limit=7, rto_ticks=4, backoff_ticks=1)
    q, n, k = 4, 8, 3
    cpay = rng.integers(0, 256, (q, n, ccfg.msg_bytes), dtype=np.uint8)
    msgs = jnp.asarray(np.stack([cpay, np.zeros_like(cpay)]))
    fault = WireFault(drop_rate=0.15, corrupt_rate=0.1, seed=7)
    dp_ab = _dp(ab, "cord")
    runs = {}
    for name, flt in (("lossless", None), ("lossy", fault)):
        parts = _conn_parts(ab, dp_ab, ccfg, q, fault=flt, credits=q * n)
        out, conn = parts["xfer"](msgs, parts["init"]())
        runs[name] = (np.asarray(out)[1], verbs.conn_snapshot(conn))
    _check(np.array_equal(runs["lossless"][0], cpay),
           "lossless conn_send delivery differs from the source")
    _check(np.array_equal(runs["lossy"][0], runs["lossless"][0]),
           "conn_send under WireFault differs from the lossless run")
    retrans = int(np.sum(runs["lossy"][1]["retransmits"]))
    _check(retrans > 0, "the seeded WireFault caused no retransmission")
    result["conn_send_retransmits"] = retrans
    print(f"conn_send {q} QPs under WireFault: {retrans} retransmissions, "
          f"delivery bit-identical to the lossless run")

    # migration chips (0, 1) -> (2, 3) partway through the transfer
    pa = _conn_parts(ab, dp_ab, ccfg, q, fault=fault, credits=q * n * 2)
    pb = _conn_parts(cd, _dp(cd, "cord"), ccfg, q, fault=fault)
    out1, conn = pa["xfer"](msgs[:, :, :k], pa["init"]())
    snap = verbs.conn_snapshot(pa["quiesce"](conn))
    _check(int(snap["cq_head"] - snap["cq_tail"]) == 0, "CQ not quiesced")
    out2, _ = pb["xfer"](msgs[:, :, k:], verbs.conn_restore(snap, cd))
    moved = np.concatenate([np.asarray(out1)[1], np.asarray(out2)[1]],
                           axis=1)
    _check(np.array_equal(moved, runs["lossless"][0]),
           "the migrated transfer differs from the uninterrupted one")
    print(f"conn migration chips (0,1) -> (2,3) after {k} of {n} messages "
          f"per QP: delivery bit-identical to the uninterrupted transfer")

    # mediated psum over all four chips against bypass
    mesh4 = compat.make_mesh((4,), ("data",), devices=devices)
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((4, 1 << 20)), jnp.float32),
        NamedSharding(mesh4, P("data")))
    sums = {}
    for mode in ("bypass", "cord"):
        dp = _dp(mesh4, mode)
        f = jax.jit(compat.shard_map(
            lambda v, dp=dp: dp.psum(v, "data")[0], mesh=mesh4,
            in_specs=P("data"), out_specs=P("data")))
        sums[mode] = np.asarray(f(x))
    _check(np.array_equal(sums["bypass"], sums["cord"]),
           "cord psum over four chips differs from bypass")
    _check(np.isfinite(sums["cord"]).all(), "non-finite psum")
    print(f"psum over 4 chips ({x.size} f32): cord bit-identical to bypass")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip verbs phase")
    ap.add_argument("--out", default=os.path.join(ROOT, "smoke_out"),
                    help="directory for artifacts (default: smoke_out/)")
    args = ap.parse_args()
    devs = platform_gate(args.chips)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {n_cached} entries at start")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(devs[:4])
    else:
        serve_phase(devs[0], args.out)
    n_after = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"phase wall time {time.perf_counter() - t0:.1f} s (smoke "
          f"number); compile cache: {n_after} entries at end")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
