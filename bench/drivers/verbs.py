"""Verbs driver: perftest-style RC sends through the program's
``windowed_send`` on several QP pairs at once, one pair of chips each.

Set-up builds the dataplane from the configuration (the slopes pinned
first), one jitted program that runs every pair's transfer of ``iters``
messages, and the traffic's payload sets on the chips, and runs the
program twice.  The window calls it back to back, cycling the payload
sets, each call ending in ``block_until_ready``, until ``--seconds`` have
passed; the rate is the messages of every call over the window's wall
time.  After the window every delivered payload is compared with its
source, bit for bit, on every pair.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

import harness as H
import tracefile
import traffic as T

TRACE_SECONDS = 2.0


def run(ctx) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import DataplaneConfig
    from repro.core import Dataplane, compat, verbs

    c, mix = ctx.cell.config, ctx.cell.traffic
    pairs = c["qp_pairs"]
    devs = [ctx.devices[i] for p in pairs for i in p]
    mesh = compat.make_mesh((len(pairs), 2), ("pair", "rank"), devices=devs)
    H.pin_slopes(ctx.slopes, jax.default_backend())
    p = c["dataplane"]
    dp = Dataplane(DataplaneConfig(mode=p["mode"], emulate_costs=True,
                                   syscall_cost_ns=p["syscall_cost_ns"],
                                   interrupt_cost_us=p["interrupt_cost_us"],
                                   policies=tuple(p["policies"])),
                   mesh=mesh)
    slopes = H.check_slopes(ctx.slopes)
    qcfg = verbs.QPConfig(transport=c["connection"],
                          msg_bytes=mix["message_bytes"],
                          depth=c["tx_depth"],
                          max_outstanding=c["tx_depth"])

    def body(m):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(qcfg)
        qp, _ = verbs.post_recv(dp, qcfg, qp, rank, dst=1, n=c["rx_depth"])
        out, _, _ = verbs.windowed_send(dp, qcfg, qp, m[0, 0], rank, src=0,
                                        dst=1)
        return out[None, None]

    spec = P("pair", "rank")
    send = jax.jit(compat.shard_map(body, mesh=mesh, in_specs=spec,
                                    out_specs=spec))
    host = T.messages(mix, ctx.words, len(pairs), c["iters"])
    sets = [jax.device_put(np.stack([h, np.zeros_like(h)], axis=1),
                           NamedSharding(mesh, spec)) for h in host]
    with jax.profiler.TraceAnnotation("bench/warmup"):
        for _ in range(2):
            send(sets[0]).block_until_ready()

    # ---- the window ----------------------------------------------------
    seconds = ctx.seconds
    t_trace = min(TRACE_SECONDS, seconds / 2)
    tracer = tracefile.Tracer(str(ctx.out_dir / "trace")) \
        if ctx.trace else None
    tr, xplane = [None, None], None
    outs = []
    setup_s = time.perf_counter() - ctx.t_start
    compiles0 = ctx.counter.compiles
    t0 = time.perf_counter()
    t_cut = t0 + seconds
    t = t0
    while t < t_cut:
        if tracer is not None:
            if tr[0] is None and t >= t0 + (seconds - t_trace) / 2:
                tracer.start()
                tr[0] = time.perf_counter()
            elif tr[1] is None and tr[0] is not None and \
                    t >= tr[0] + t_trace:
                tr[1] = time.perf_counter()
                xplane = tracer.stop()
        k = len(outs) % len(sets)
        with jax.profiler.TraceAnnotation("bench/call"):
            out = send(sets[k])
            out.block_until_ready()
        outs.append((k, out))
        t = time.perf_counter()
    t_end = t
    if tracer is not None and tr[0] is not None and tr[1] is None:
        tr[1] = time.perf_counter()
        xplane = tracer.stop()
    in_window = ctx.counter.compiles - compiles0
    if in_window:
        raise H.BenchError(f"{in_window} programs compiled inside the "
                           f"window: {ctx.counter.names[-in_window:]}")
    device = H.device_info(ctx.devices)
    sent = len(outs) * c["iters"] * len(pairs)
    e2e = {"msg_rate": sent / (t_end - t0), "setup_s": setup_s}

    # ---- correctness: every delivery against its source ------------------
    limit = c["check"]["mismatch_limit"]
    verdicts = {"program": judge(outs, host, limit, swap=False)}
    if getattr(ctx, "control", None):
        # RC's in-order guarantee broken: each call's first two messages
        # delivered the other way round, judged in the program's place
        verdicts["control"] = judge(outs, host, limit, swap=True)
    judged = verdicts["control" if "control" in verdicts else "program"]
    bad = judged["bad"]
    checks = [{"name": "mismatched_messages", "value": bad, "limit": limit,
               "rule": f"delivered payload differs from its source, bit for "
                       f"bit, over {sent} messages on {len(pairs)} QPs"}]
    record = {"config": c, "traffic": mix, "peaks": ctx.peaks,
              "trace": None}
    if xplane is not None:
        record["trace"] = tracefile.reduce_xplane(xplane)
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
        kern = p.get("kernels", [])
        if kern and not tracefile.kernel_totals(record["trace"], kern)[1]:
            raise H.BenchError(f"no dataplane kernel ({kern}) ran in the "
                               f"traced window of a mediated cell")
    return {"e2e": e2e, "record": record, "device": device,
            "correct": judged["correct"], "attempted": sent,
            "failed": bad, "checks": checks, "verdicts": verdicts,
            "setup": {"compiles": ctx.counter.compiles - in_window,
                      "cache_hits": ctx.counter.hits, "slopes": slopes}}


def judge(outs, host, limit: int, swap: bool) -> dict:
    """Mismatched deliveries over every call ``(set, out)``; with ``swap``
    the first two messages of each call change places first."""
    bad = 0
    for k, out in outs:
        got = np.asarray(out)[:, 1]
        if swap:
            got = got.copy()
            got[:, [0, 1]] = got[:, [1, 0]]
        bad += mismatches(got, host[k])
    return {"correct": bool(outs) and bad <= limit, "bad": bad}


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Messages ``(pairs, n, bytes)`` that differ from their source in any
    byte."""
    return int((got != want).any(axis=-1).sum())


__all__ = ["run", "judge", "mismatches"]
