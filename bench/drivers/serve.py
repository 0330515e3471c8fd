"""Serve driver: a decoder LM served as an offline batch job through the
program's continuous, paged engine on a one-chip dataplane.

Set-up makes the weights on the chip from the seed, builds the dataplane
from the configuration (the slopes pinned first), and warms every prompt
shape the mix can produce.  The window then serves one seeded backlog,
closed loop, and is cut at exactly ``--seconds``: tokens count by their
own host stamps, and the engine is stopped at the first tick past the
cut through its public ``on_tick`` hook once the requests finished by
then hold the traffic's ``check_tokens`` served tokens (it serves on,
untimed, past the cut until they do).  A sample of the finished requests
(the longest among them) is then replayed through the configuration's
float32 reference and every served token is judged by how far its
reference logit lies below the reference's best.  With a control
(``ctx.control``, a dtype narrower than the served one), the token that
the reference computed in that type ranks first at each position takes
the served token's place and goes through the same judgement.

The engine surface used is ``Engine``, ``Request``, ``Engine.run`` and
``on_tick`` (which fires only with a ``CounterTimeline`` attached).
"""

from __future__ import annotations

import gc
import math
import shutil
import time

import numpy as np

import harness as H
import tracefile
import traffic as T

# length of the traced stretch, centred in the window
TRACE_SECONDS = 4.0
# longest the engine serves on past the cut so that requests in flight
# finish and can be checked (nothing after the cut is timed)
DRAIN_SECONDS = 90.0


class WindowClosed(Exception):
    """Raised from ``on_tick`` at the first tick past the cut."""


class Stamped(list):
    """A request's ``out_tokens``: stamps the host time of every append."""

    def __init__(self):
        super().__init__()
        self.t: list[float] = []

    def append(self, tok) -> None:
        self.t.append(time.perf_counter())
        super().append(tok)


# ---------------------------------------------------------------------------
# the configuration, as the program takes it
# ---------------------------------------------------------------------------

def model_config(c: dict):
    """The program's ``ModelConfig`` for the configuration file, refusing
    a file that states what the program cannot run."""
    from repro.configs.base import AttentionConfig, ModelConfig
    d, h = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    fixed = {"embedding_multiplier": math.sqrt(d),
             "attention_multiplier": 1 / math.sqrt(hd),
             "residual_multiplier": 1.0, "logits_scaling": 1.0,
             "hidden_act": "silu", "tie_word_embeddings": True}
    for k, v in fixed.items():
        ok = (math.isclose(c[k], v, rel_tol=1e-12)
              if isinstance(v, float) else c[k] == v)
        if not ok:
            raise H.BenchError(f"the program runs {k} = {v!r}; the "
                               f"configuration states {c[k]!r}")
    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=d, d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        attention=AttentionConfig(num_heads=h,
                                  num_kv_heads=c["num_key_value_heads"],
                                  head_dim=hd, rope_theta=c["rope_theta"]),
        max_seq_len=c["max_position_embeddings"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=True, act_fn="silu", gated_mlp=True,
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"])


def dataplane(c: dict, devices, tenants):
    from repro.configs.base import DataplaneConfig
    from repro.core import Dataplane
    from repro.launch.mesh import make_local_mesh
    p = c["dataplane"]
    cfg = DataplaneConfig(mode=p["mode"], emulate_costs=True,
                          syscall_cost_ns=p["syscall_cost_ns"],
                          interrupt_cost_us=p["interrupt_cost_us"],
                          policies=tuple(p["policies"]))
    return Dataplane(cfg, mesh=make_local_mesh(devices), tenant=tenants[0],
                     tenants=tuple(tenants))


def warm_lengths(mix: dict, resumes: bool) -> list[int]:
    """One prompt length per prefill shape the mix can produce: the powers
    of two up to one chunk, then each whole number of chunks, from its
    shortest prompt to its longest, or, where ``resumes`` (the pool can
    preempt a slot), to the longest re-prefill after a preemption
    (prompt max + output max - 1)."""
    lens = T.lognormal_quantiles(mix["prompt"], mix["block"])
    lo = min(lens)
    hi = max(lens) + (mix["output"]["max"] - 1 if resumes else 0)
    chunk = mix["engine"]["prefill_chunk"]
    out, p = [], 8
    while p < lo:
        p *= 2
    while p <= min(hi, chunk):
        out.append(p)
        p *= 2
    m = 2 * chunk
    while m - chunk < hi:
        out.append(min(m, hi))
        m += chunk
    return out


def tenant_kernel_iters(dp, device, tenant: str) -> float:
    """In-kernel delay iterations the dataplane charges ``tenant`` for one
    mediated one-chip ``psum``, from its per-tenant runtime counters."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import compat

    def body(v, rt):
        return dp.psum(v, "data", state=rt, tenant=tenant)

    f = jax.jit(compat.shard_map(body, mesh=dp.mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P())))
    x = jax.device_put(jnp.arange(4096, dtype=jnp.float32), device)
    _, rt = f(x, dp.runtime_init())
    return float(dp.runtime_report(rt)[tenant]["kernel_iters"])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(ctx) -> dict:
    import jax

    from repro.configs.base import ServeConfig
    from repro.core import CounterTimeline
    from repro.models import build_model
    from repro.serve import Engine, Request

    c, mix = ctx.cell.config, ctx.cell.traffic
    ref = ctx.cell.reference()
    eng_p = mix["engine"]
    dev = ctx.devices[0]
    H.pin_slopes(ctx.slopes, jax.default_backend())

    mcfg = model_config(c)
    weights = ref.init_weights(c, ctx.words, dev)
    dp = dataplane(c, [dev], mix["tenants"])
    slopes = H.check_slopes(ctx.slopes)
    if jax.default_backend() == "tpu" and \
            c["dataplane"]["mode"] != "bypass" and not dp.pipeline.pallas:
        raise H.BenchError("the mediated dataplane did not select the "
                           "Pallas kernels")
    scfg = ServeConfig(max_batch=eng_p["max_batch"],
                       prefill_chunk=eng_p["prefill_chunk"],
                       max_new_tokens=mix["output"]["max"],
                       temperature=eng_p["temperature"],
                       kv_cache_len=eng_p["kv_len"],
                       block_size=eng_p["block_size"],
                       n_blocks=c["serve"]["n_blocks"])
    eng = Engine(build_model(mcfg), weights, mcfg, scfg, dp=dp, eos_id=-1,
                 obs=CounterTimeline(source=f"bench/{ctx.cell.name}"))

    kernel_iters = tenant_kernel_iters(dp, dev, mix["tenants"][-1])
    resumes = c["serve"]["n_blocks"] * eng_p["block_size"] < \
        eng_p["max_batch"] * eng_p["kv_len"]
    rng = np.random.default_rng(ctx.words)
    prompts = [rng.integers(0, c["vocab_size"], n, dtype=np.int32)
               for n in warm_lengths(mix, resumes)]

    def warm(i, new):
        return Request(rid=-1 - i, prompt=prompts[i], max_new_tokens=new,
                       tenant=mix["tenants"][0])

    # a fresh pool is an uncommitted array, and a program compiles once
    # for it and once for the pools it returns: so each prompt shape runs
    # first alone, on a fresh pool, then all together in order (one
    # tenant, so first in, first served) behind a request that decodes
    with jax.profiler.TraceAnnotation("bench/warmup"):
        for i in range(len(prompts)):
            eng.run([warm(i, 1)])
        eng.run([warm(0, 2)] + [warm(i, 1) for i in range(len(prompts))])
    backlog = [Request(rid=i, prompt=r["prompt"],
                       max_new_tokens=r["max_new_tokens"],
                       tenant=r["tenant"], out_tokens=Stamped())
               for i, r in enumerate(T.requests(mix, ctx.words,
                                                c["vocab_size"]))]
    setup_compiles, setup_hits = ctx.counter.compiles, ctx.counter.hits

    # ---- the window ----------------------------------------------------
    seconds = ctx.seconds
    t_trace = min(TRACE_SECONDS, seconds / 2)
    tracer = tracefile.Tracer(str(ctx.out_dir / "trace")) \
        if ctx.trace else None
    st = {"ticks": 0, "tr": [None, None], "xplane": None}

    def drained(t) -> bool:
        """Past the cut, serving goes on (untimed) until the finished
        requests hold enough served tokens to check, or DRAIN_SECONDS."""
        done = sum(len(r.out_tokens) for r in backlog if r.done)
        return done >= mix["check_tokens"] or t >= t_cut + DRAIN_SECONDS
    setup_s = time.perf_counter() - ctx.t_start
    t0 = time.perf_counter()
    t_cut = t0 + seconds

    def on_tick(_engine):
        t = time.perf_counter()
        if t <= t_cut:
            st["ticks"] += 1
        elif not drained(t):
            return
        if tracer is not None:
            if st["tr"][0] is None and t >= t0 + (seconds - t_trace) / 2:
                tracer.start()
                st["tr"][0] = time.perf_counter()
            elif st["tr"][1] is None and st["tr"][0] is not None and \
                    t >= st["tr"][0] + t_trace:
                st["tr"][1] = time.perf_counter()
                st["xplane"] = tracer.stop()
        if t >= t_cut:
            raise WindowClosed

    eng.on_tick = on_tick
    compiles0 = ctx.counter.compiles
    try:
        with jax.profiler.TraceAnnotation("bench/run"):
            eng.run(backlog)
    except WindowClosed:
        pass
    else:
        raise H.BenchError("the backlog ran dry before the window closed")
    finally:
        if tracer is not None and st["tr"][0] is not None and \
                st["tr"][1] is None:
            st["tr"][1] = time.perf_counter()
            st["xplane"] = tracer.stop()
    in_window = ctx.counter.compiles - compiles0
    if in_window:
        raise H.BenchError(f"{in_window} programs compiled inside the "
                           f"window: {ctx.counter.names[-in_window:]}")
    device = H.device_info([dev])
    preempted = int(sum(v["preemptions"]
                        for v in eng.tenant_report().values()))

    # ---- what the window did --------------------------------------------
    for r in backlog:
        if len(r.out_tokens.t) != len(r.out_tokens):
            raise H.BenchError(f"request {r.rid}: {len(r.out_tokens)} "
                               f"tokens, {len(r.out_tokens.t)} stamps")
    w = window_stats([(len(r.prompt), r.out_tokens.t) for r in backlog],
                     t0, seconds)
    if len(w["gaps"]) < 20:
        raise H.BenchError(f"only {len(w['gaps'])} token gaps in the window")
    e2e = {"tok_s": w["tok_s"], "itl_p95_ms": w["itl_p95_ms"],
           "setup_s": setup_s}

    # ---- correctness: free the program's state, then the reference -------
    eng = dp = None
    gc.collect()
    t_check = time.perf_counter()
    finished = [r for r in backlog if r.done]
    sample = choose_sample(finished, mix["check_tokens"], ctx.words)
    limit = c["check"]["gap_limit"]
    lower = getattr(ctx, "control", None)
    gaps = {"program": [], "control": []}
    for r in sample:
        if len(r.out_tokens) != r.max_new_tokens:
            gaps["program"].append(None)
            gaps["control"].append(None)
            continue
        g, g_ctl = token_gaps(ref, weights, c, r.prompt, list(r.out_tokens),
                              control=lower)
        gaps["program"].append(g)
        gaps["control"].append(g_ctl)
    verdicts = {k: verdict(g, limit) for k, g in gaps.items()
                if k == "program" or lower}
    # the control, where asked for, is judged in the program's place
    judged = verdicts["control" if lower else "program"]
    checks = [{"name": "widest_logit_gap", "value": judged["widest"],
               "limit": limit,
               "rule": "served token's reference logit below the "
                       "reference's best, over "
                       f"{judged['tokens']} tokens of {len(sample)} "
                       f"requests"}]
    check_s = time.perf_counter() - t_check

    record = {"config": c, "traffic": mix, "peaks": ctx.peaks,
              "tokens": w["tokens"], "decode_tokens": w["decode_tokens"],
              "ticks": st["ticks"], "max_batch": eng_p["max_batch"],
              "itl_p95_ms": w["itl_p95_ms"],
              "traced_host": tuple(st["tr"]), "trace": None}
    if st["xplane"] is not None:
        record["trace"] = tracefile.reduce_xplane(st["xplane"])
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
        kern = c["dataplane"].get("kernels", [])
        if kern and not tracefile.kernel_totals(record["trace"], kern)[1]:
            raise H.BenchError(f"no dataplane kernel ({kern}) ran in the "
                               f"traced window of a mediated cell")
    return {"e2e": e2e, "record": record, "device": device,
            "correct": judged["correct"], "attempted": w["started"],
            "failed": judged["bad"], "checks": checks, "verdicts": verdicts,
            "window": {"ticks": st["ticks"], "tokens": len(w["tokens"]),
                       "decode_tokens": w["decode_tokens"],
                       "gaps": len(w["gaps"]), "started": w["started"],
                       "finished_by_cut": sum(
                           1 for r in backlog
                           if r.done and r.out_tokens.t[-1] <= t_cut),
                       "finished": len(finished), "preemptions": preempted,
                       "check_s": check_s},
            "setup": {"compiles": setup_compiles, "cache_hits": setup_hits,
                      "slopes": slopes,
                      "tenant_kernel_iters": kernel_iters}}


def window_stats(stamps, t0: float, seconds: float) -> dict:
    """What a window of ``seconds`` from ``t0`` served, from each request's
    ``(prompt_len, token stamps)``: ``tokens`` (stamp, context) inside the
    cut, ``gaps`` between consecutive tokens of one request both inside
    it, requests ``started``, ``decode_tokens`` (all but each request's
    first), ``tok_s`` and ``itl_p95_ms``."""
    t_cut = t0 + seconds
    tokens, gaps, started, decode = [], [], 0, 0
    for p, ts in stamps:
        ts = [t for t in ts if t0 <= t <= t_cut]
        started += bool(ts)
        tokens += [(t, p + j) for j, t in enumerate(ts)]
        decode += max(len(ts) - 1, 0)
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    return {"tokens": tokens, "gaps": gaps, "started": started,
            "decode_tokens": decode, "tok_s": len(tokens) / seconds,
            "itl_p95_ms": (1e3 * float(np.percentile(gaps, 95))
                           if gaps else float("nan"))}


def verdict(gaps: list, limit: float) -> dict:
    """The judgement of one sample: ``gaps`` holds, per request, the gap
    of each judged token (``None`` for a request that did not serve all
    its tokens, which fails).  ``correct`` where there is a sample and no
    request reads a gap over ``limit``."""
    widest, tokens, bad = 0.0, 0, 0
    for g in gaps:
        if g is None:
            bad += 1
            continue
        tokens += len(g)
        widest = max(widest, float(g.max()))
        bad += float(g.max()) > limit
    return {"correct": bool(gaps) and bad == 0, "widest": widest,
            "tokens": tokens, "bad": bad}


def choose_sample(finished, min_tokens: int, words) -> list:
    """The finished request with the most positions, then others in a
    seeded order until ``min_tokens`` served tokens are covered."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r.prompt)
                                              + len(r.out_tokens)))
    rest = order[1:]
    np.random.default_rng([*words, 1]).shuffle(rest)
    out, n = [order[0]], len(order[0].out_tokens)
    for r in rest:
        if n >= min_tokens:
            break
        out.append(r)
        n += len(r.out_tokens)
    return out


def token_gaps(ref, weights, c, prompt, served, control=None):
    """Per served token: the reference's best logit minus its logit of the
    served token, the reference fed the prompt and the served tokens.
    With ``control`` (a narrower dtype name), also the same gap for the
    token the reference computed in that type ranks first at each
    position; else ``None``."""
    p = len(prompt)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    at = p - 1 + np.arange(len(served))
    want = ref.logits_at(weights, c, seq, at)
    rows = np.arange(len(served))
    gaps = want.max(-1) - want[rows, np.asarray(served)]
    if control is None:
        return gaps, None
    import jax.numpy as jnp
    tok = ref.logits_at(weights, c, seq, at,
                        compute=jnp.dtype(control)).argmax(-1)
    return gaps, want.max(-1) - want[rows, tok]


__all__ = ["run", "model_config", "warm_lengths", "tenant_kernel_iters",
           "token_gaps", "verdict", "choose_sample", "Stamped",
           "WindowClosed"]
