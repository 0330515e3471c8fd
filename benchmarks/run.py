"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus the full
JSON rows to runs/bench_results.json.  Benchmarks with per-tenant runtime
accounting also emit schema-versioned ``runs/*_timeline.json`` artifacts
(core/obs.py, docs/observability.md) next to the bench JSON.

Sections:
  fig1      — technique-removal latency/throughput (paper Fig. 1)
  fig3/fig4 — CoRD overhead matrix & relative throughput (Figs. 3-4)
  window    — CQ-runtime bandwidth vs. sender-window depth (RC + UD)
  credits   — credit flow-control ablation (stall counters)
  converged — train job + serve tenants on ONE dataplane under QoS
              arbitration (the converged-cloud scenario)
  fig5      — system-A preset (Fig. 5)
  fig6      — NPB suite bypass/cord/socket (Fig. 6)
  kernels   — Pallas kernel correctness + XLA timings
  roofline  — dry-run roofline terms (if runs/dryrun is populated)

Requires >=8 CPU devices: the driver re-execs itself with the XLA flag if
needed, so ``PYTHONPATH=src python -m benchmarks.run [--fast]`` suffices.
"""

from __future__ import annotations

import json
import os
import sys

from benchmarks._bootstrap import ensure_host_devices

ensure_host_devices(8, module="benchmarks.run")


def accumulate_report(totals: dict, report: dict) -> dict:
    """Fold one per-tenant counter report into host-side cumulative
    totals — additive columns sum, the ``cq_depth`` high-water mark takes
    the max.  Used wherever the dry-run smokes rebuild a cumulative
    timeline from repeated fresh-state transfers."""
    for tenant, ctrs in report.items():
        acc = totals.setdefault(tenant, dict.fromkeys(ctrs, 0.0))
        for k, v in ctrs.items():
            acc[k] = max(acc[k], v) if k == "cq_depth" else acc[k] + v
    return totals


def dry_run() -> None:
    """CI smoke: build the measured paths and execute a minimal slice of
    each — perftest ping-pong over the verbs layer, one NPB kernel in
    bypass+cord, and a per-tenant counter timeline over repeated windowed
    transfers, asserting the emitted artifact is well-formed — without
    the full figure sweeps."""
    import jax
    import jax.numpy as jnp

    from benchmarks import npb, perftest
    from repro.core.obs import CounterTimeline

    mesh2 = perftest.make_mesh2()
    dp = perftest._dp("cord", emulate=True, mesh=mesh2)
    lat = perftest.pingpong_latency_us(mesh2, dp, dp, 1024, iters=4)
    print(json.dumps({"table": "dryrun", "pingpong_us": round(lat, 2),
                      "pipeline": list(dp.pipeline.stage_names)}))
    gbps, rate, stats = perftest.windowed_throughput(
        mesh2, dp, dp, 1024, window=4, n_msgs=8)
    print(json.dumps({"table": "dryrun", "windowed_gbps": round(gbps, 3),
                      **stats}))

    # timeline smoke: several windowed transfers, each from a fresh
    # runtime state (build_windowed's body already allreduce_state-sums
    # its state over the mesh — feeding that aggregate back in would
    # re-psum it every call), with host-side accumulation into cumulative
    # per-tenant totals between calls; assert the saved artifact
    # round-trips as schema-valid with an honest, constant-work rate
    # series per tenant
    fn, _ = perftest.build_windowed(mesh2, dp, dp, 1024, n_msgs=8, window=4)
    msgs = jnp.zeros((2, 8, 1024), jnp.uint8)
    rt0 = dp.runtime_init()
    totals: dict[str, dict[str, float]] = {}
    timeline = CounterTimeline(source="bench-dryrun")
    for i in range(1, 5):
        _, _, rt = jax.block_until_ready(fn(msgs, rt0))
        accumulate_report(totals, dp.runtime_report(rt))
        timeline.snapshot(i, {t: dict(a) for t, a in totals.items()})
    path = timeline.save("runs/dryrun_timeline.json")
    doc = CounterTimeline.load(path)             # schema validation
    rates = doc["rates"][dp.tenant]
    assert len(rates["ops_s"]) == 3 and all(rates["ops_s"]), rates
    # identical transfers must account identical work per window — a
    # doubling series here means state got re-aggregated somewhere
    ops = [s["tenants"][dp.tenant]["ops"] for s in doc["samples"]]
    deltas = [b - a for a, b in zip(ops, ops[1:])]
    assert deltas and all(d == deltas[0] for d in deltas), ops
    print(json.dumps({"table": "dryrun", "timeline": path,
                      "samples": len(doc["samples"]),
                      "ops_s_last": round(rates["ops_s"][-1], 1)}))

    elastic_smoke()
    control_plane_smoke()
    bounce_smoke()
    transport_smoke()

    # converged train+serve contention smoke (benchmarks/converged.py):
    # serve tenants must keep nonzero tok/s while the QoS-throttled train
    # job runs on the same dataplane
    from benchmarks import converged
    converged.dry_run()

    for row in npb.run_all(benches=("EP",), modes=("bypass", "cord")):
        print(json.dumps(row))
    print("dry-run ok")


def elastic_smoke() -> None:
    """PR-5 acceptance smoke (docs/elasticity.md): a sustained ``denied``
    rate trips the ThresholdWatcher exactly once (hysteresis + cooldown
    hold), a windowed transfer in flight at trigger time survives a live
    QP migration onto a *different* 2-rank mesh bit-identically, and the
    saved v2 timeline artifact validates with the remesh event
    recorded."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import perftest
    from repro.configs.base import DataplaneConfig
    from repro.core import compat, verbs
    from repro.core.dataplane import Dataplane
    from repro.core.obs import CounterTimeline, ThresholdWatcher
    from repro.core.policies import QuotaPolicy, TelemetryPolicy

    n_msgs, msg_bytes, window = 8, 1024, 4
    mesh_a = perftest.make_mesh2()
    mesh_b = compat.make_mesh((2,), ("rank",), devices=jax.devices()[2:4])

    def dp_on(mesh):
        # observe-only quota: each round's runtime bytes blow a 2 KiB
        # budget, so the denied counter climbs every round — the
        # sustained trigger signal
        return Dataplane(
            DataplaneConfig(mode="cord", emulate_costs=True), mesh=mesh,
            policies=[TelemetryPolicy(),
                      QuotaPolicy(hard=False, limits={"default": 2048})])

    dp_a, dp_b = dp_on(mesh_a), dp_on(mesh_b)
    payload = np.arange(n_msgs * msg_bytes, dtype=np.uint8) \
        .reshape(n_msgs, msg_bytes)
    msgs = jnp.asarray(np.stack([payload, np.zeros_like(payload)]))
    conn_a = perftest.build_migratable(mesh_a, dp_a, msg_bytes, window,
                                       credits=n_msgs)
    conn_b = perftest.build_migratable(mesh_b, dp_b, msg_bytes, window)

    # --- watched run: repeated transfers, denied% sustained over the
    # threshold in EVERY window; hysteresis must fire exactly once ------
    timeline = CounterTimeline(source="bench-elastic")
    watcher = ThresholdWatcher({"denied_pct": 40.0}, sustain=2, cooldown=16)
    totals: dict[str, dict[str, float]] = {}
    for i in range(1, 7):
        qp, _ = conn_a["init"](dp_a.runtime_init())
        _, _, rt = jax.block_until_ready(
            conn_a["xfer"](msgs, qp, dp_a.runtime_init()))
        accumulate_report(totals, dp_a.runtime_report(rt))
        timeline.snapshot(i, {t: dict(a) for t, a in totals.items()},
                          gauges=watcher.gauges())
        for ev in watcher.observe(timeline):
            timeline.record_event(ev["kind"], ev["step"],
                                  tenant=ev["tenant"], t=ev["t"],
                                  detail=ev["detail"])
    assert len(watcher.triggers) == 1, \
        f"hysteresis broke: {len(watcher.triggers)} triggers, expected 1"
    trigger_step = watcher.triggers[0]["step"]
    assert trigger_step == 1 + watcher.sustain, watcher.triggers

    # --- the response: live QP migration of an in-flight transfer ------
    # baseline: one uninterrupted transfer on mesh A
    qp, _ = conn_a["init"](dp_a.runtime_init())
    full_out, qp_full, _ = jax.block_until_ready(
        conn_a["xfer"](msgs, qp, dp_a.runtime_init()))
    # migrated: half on mesh A, quiesce → stop-and-copy → restore on
    # mesh B, the rest there — outstanding credits ride along
    k = n_msgs // 2
    qp, _ = conn_a["init"](dp_a.runtime_init())
    out1, qp, _ = conn_a["xfer"](msgs[:, :k], qp, dp_a.runtime_init())
    qp, _ = conn_a["quiesce"](qp, dp_a.runtime_init())
    snap = verbs.qp_snapshot(qp)
    assert int(snap["cq_head"] - snap["cq_tail"]) == 0, "CQ not quiesced"
    assert int(snap["credits"]) == n_msgs - k, "credits lost in migration"
    qp_b = verbs.qp_restore(snap, mesh_b)
    out2, qp_b, _ = jax.block_until_ready(
        conn_b["xfer"](msgs[:, k:], qp_b, dp_b.runtime_init()))
    moved = np.concatenate([np.asarray(out1)[1], np.asarray(out2)[1]])
    np.testing.assert_array_equal(moved, np.asarray(full_out)[1])
    snap_b, snap_f = verbs.qp_snapshot(qp_b), verbs.qp_snapshot(qp_full)
    for key in ("sq_head", "cq_sent", "credits", "rx_owed"):
        assert int(snap_b[key]) == int(snap_f[key]), \
            f"{key} diverged across the migration"
    timeline.record_event(
        "remesh", trigger_step, tenant="default",
        detail={"from": "mesh_a", "to": "mesh_b", "migrated_msgs": k})

    # --- the artifact records the whole loop ---------------------------
    path = timeline.save("runs/elastic_timeline.json")
    doc = CounterTimeline.load(path)              # schema validation (v2)
    kinds = [e["kind"] for e in doc["events"]]
    assert kinds.count("trigger") == 1 and kinds.count("remesh") == 1, kinds
    print(json.dumps({"table": "dryrun", "elastic_timeline": path,
                      "trigger_step": trigger_step,
                      "migrated_bit_identical": True,
                      "events": kinds}))


def control_plane_smoke() -> None:
    """Pod-scale control-plane smoke (docs/elasticity.md): two "hosts"
    — disjoint 2-device meshes, one carrying quota-metered train-side
    verbs traffic, the other a real serving engine with a rate-limited
    tenant — stream per-process timelines that merge step-aligned into
    ONE pod timeline each round.  A :class:`WatcherGroup` runs a
    train-remesh watcher and a serve-budget watcher over the merged
    rates:

    * the noisy phase trips BOTH.  The train response live-migrates an
      in-flight windowed QP transfer onto the spare mesh (shrink); the
      serve response halves the engine's per-tenant slot budget.
    * the quiet phase fires both release arms: the still-in-flight
      transfer migrates BACK onto its original mesh (grow) and the
      budget is restored — the closed shrink→recover→grow cycle.

    The migrated transfer must complete bit-identically to an
    uninterrupted one across BOTH migrations, and the saved merged pod
    artifact must validate with the full trigger→remesh(shrink)→
    recover→remesh(grow) sequence plus both budget moves recorded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import perftest
    from repro.configs.base import (DataplaneConfig, ElasticConfig,
                                    ServeConfig)
    from repro.core import compat, verbs
    from repro.core.dataplane import Dataplane
    from repro.core.obs import (CounterTimeline, ThresholdWatcher,
                                WatcherGroup, merge_timelines)
    from repro.core.policies import QoSPolicy, QuotaPolicy, TelemetryPolicy
    from repro.runtime import ServeElasticController

    n_msgs, msg_bytes, window = 8, 1024, 4
    mesh_a = perftest.make_mesh2()
    mesh_b = compat.make_mesh((2,), ("rank",), devices=jax.devices()[2:4])
    # host 0: train-side traffic over an observe-only 2 KiB quota — every
    # noisy round blows the budget, so denied_pct sustains over threshold
    dp_a = Dataplane(
        DataplaneConfig(mode="cord", emulate_costs=True), mesh=mesh_a,
        policies=[TelemetryPolicy(),
                  QuotaPolicy(hard=False, limits={"default": 2048})])
    dp_b = Dataplane(DataplaneConfig(mode="cord", emulate_costs=True),
                     mesh=mesh_b, policies=[TelemetryPolicy()])
    conn_a = perftest.build_migratable(mesh_a, dp_a, msg_bytes, window,
                                       credits=n_msgs)
    conn_b = perftest.build_migratable(mesh_b, dp_b, msg_bytes, window)
    payload = np.arange(n_msgs * msg_bytes, dtype=np.uint8) \
        .reshape(n_msgs, msg_bytes)
    msgs = jnp.asarray(np.stack([payload, np.zeros_like(payload)]))

    # host 1: a real engine whose "burst" tenant is admission-limited, so
    # its deferrals (the throttled column) climb while requests queue
    from repro.configs import get_model_config
    from repro.models import build_model
    from repro.serve import Engine, Request

    cfg = get_model_config("gemma3-1b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dp_serve = Dataplane(
        DataplaneConfig(mode="cord", emulate_costs=True),
        mesh=compat.make_mesh((8,), ("data",)), tenant="steady",
        tenants=("steady", "burst"),
        policies=[TelemetryPolicy(),
                  QoSPolicy(rates={"burst": 0.1}, burst=1.0)])
    eng = Engine(model, params, cfg,
                 ServeConfig(max_batch=2, max_new_tokens=4,
                             kv_cache_len=64),
                 dp=dp_serve, eos_id=-1)

    tl_a = CounterTimeline(source="host0")   # controller host: events land here
    tl_b = CounterTimeline(source="host1")
    group = WatcherGroup({
        "train": ThresholdWatcher({"denied_pct": 40.0}, sustain=2,
                                  cooldown=1, tenants=("default",),
                                  release={"denied_pct": 5.0},
                                  release_sustain=2, release_cooldown=8),
        "serve": ThresholdWatcher({"throttled_pct": 10.0}, sustain=2,
                                  cooldown=1, tenants=("burst",),
                                  release={"throttled_pct": 1.0},
                                  release_sustain=2, release_cooldown=8),
    })
    serve_ctl = ServeElasticController(
        ElasticConfig(enabled=True, shrink_factor=2), tl_a, eng)

    # the in-flight migratable transfer and its uninterrupted baseline
    qp_full, _ = conn_a["init"](dp_a.runtime_init())
    full_out, qp_full, _ = jax.block_until_ready(
        conn_a["xfer"](msgs, qp_full, dp_a.runtime_init()))
    k1, k2 = 3, 6                       # migration points: A | B | A again
    parts: list[np.ndarray] = []
    qp_live = None                      # in-flight QP, wherever it lives

    def wave(i):
        return [Request(rid=10 * i + j,
                        prompt=np.asarray((np.arange(8) + i + j) % 97,
                                          np.int32),
                        max_new_tokens=4,
                        tenant="burst" if j == 2 else "steady")
                for j in range(3)]

    totals: dict[str, dict[str, float]] = {}
    seen: list[str] = []                # the pod-level event storyline
    for i in range(1, 7):
        noisy = i <= 3
        if noisy:
            # host 0 under pressure: a fresh quota-blowing transfer
            qp, _ = conn_a["init"](dp_a.runtime_init())
            _, _, rt = jax.block_until_ready(
                conn_a["xfer"](msgs, qp, dp_a.runtime_init()))
            accumulate_report(totals, dp_a.runtime_report(rt))
            eng.run(wave(i))            # host 1 under pressure too
        else:
            # post-shrink quiet: host 0's tenant now runs clean on the
            # spare mesh (no quota there), host 1 goes idle
            if qp_live is not None and len(parts) == 1:
                out, qp_live, rt = jax.block_until_ready(conn_b["xfer"](
                    msgs[:, k1:k2], qp_live, dp_b.runtime_init()))
                parts.append(np.asarray(out)[1])
            else:
                qp, _ = conn_b["init"](dp_b.runtime_init())
                _, _, rt = jax.block_until_ready(
                    conn_b["xfer"](msgs, qp, dp_b.runtime_init()))
            accumulate_report(totals, dp_b.runtime_report(rt))
        tl_a.snapshot(i, {t: dict(a) for t, a in totals.items()},
                      gauges=group.gauges(), t=float(i))
        tl_b.snapshot_block(i, *eng.runtime_counters(), t=float(i))

        pod = merge_timelines([tl_a, tl_b], source="pod")
        evs = group.observe(pod, record=False)
        for ev in evs["train"] + evs["serve"]:
            tl_a.record_event(ev["kind"], ev["step"], tenant=ev["tenant"],
                              t=ev["t"], detail=ev["detail"])
            seen.append(f"{ev['detail']['watcher']}:{ev['kind']}")
        for ev in evs["train"]:
            if ev["kind"] == "trigger":
                # shrink response: migrate the in-flight transfer A → B
                qp_live, _ = conn_a["init"](dp_a.runtime_init())
                out, qp_live, _ = conn_a["xfer"](msgs[:, :k1], qp_live,
                                                 dp_a.runtime_init())
                parts.append(np.asarray(out)[1])
                qp_live, _ = conn_a["quiesce"](qp_live, dp_a.runtime_init())
                snap = verbs.qp_snapshot(qp_live)
                assert int(snap["credits"]) == n_msgs - k1, snap["credits"]
                qp_live = verbs.qp_restore(snap, mesh_b)
                tl_a.record_event("remesh", i, tenant="default",
                                  t=float(i) + 0.5,
                                  detail={"watcher": "train",
                                          "direction": "shrink",
                                          "from": "mesh_a", "to": "mesh_b",
                                          "migrated_msgs": k1})
                seen.append("train:remesh-shrink")
            elif ev["kind"] == "recover":
                # grow-back: migrate the STILL-in-flight transfer B → A
                qp_live, _ = conn_b["quiesce"](qp_live, dp_b.runtime_init())
                snap = verbs.qp_snapshot(qp_live)
                assert int(snap["credits"]) == n_msgs - k2, snap["credits"]
                qp_live = verbs.qp_restore(snap, mesh_a)
                out, qp_live, _ = jax.block_until_ready(conn_a["xfer"](
                    msgs[:, k2:], qp_live, dp_a.runtime_init()))
                parts.append(np.asarray(out)[1])
                tl_a.record_event("remesh", i, tenant="default",
                                  t=float(i) + 0.5,
                                  detail={"watcher": "train",
                                          "direction": "grow",
                                          "from": "mesh_b", "to": "mesh_a",
                                          "migrated_msgs": n_msgs - k2})
                seen.append("train:remesh-grow")
        serve_ctl.respond(evs["serve"])

    # the storyline closed in order, once each
    assert seen == ["train:trigger", "serve:trigger", "train:remesh-shrink",
                    "train:recover", "serve:recover", "train:remesh-grow"] \
        or seen == ["train:trigger", "serve:trigger", "train:remesh-shrink",
                    "serve:recover", "train:recover", "train:remesh-grow"], \
        seen
    assert serve_ctl.shrinks == 1 and serve_ctl.grows == 1
    assert eng.slot_budget() == 2, eng.slot_budget()   # restored

    # bit-identical across BOTH migrations
    moved = np.concatenate(parts)
    np.testing.assert_array_equal(moved, np.asarray(full_out)[1])
    snap_l, snap_f = verbs.qp_snapshot(qp_live), verbs.qp_snapshot(qp_full)
    for key in ("sq_head", "cq_sent", "credits", "rx_owed"):
        assert int(snap_l[key]) == int(snap_f[key]), \
            f"{key} diverged across shrink+grow migration"

    # the merged pod artifact records the whole cycle
    pod = merge_timelines([tl_a, tl_b], source="pod")
    path = pod.save("runs/control_plane_timeline.json")
    doc = CounterTimeline.load(path)             # schema validation (v2)
    kinds = [e["kind"] for e in doc["events"]]
    dirs = [e["detail"]["direction"] for e in doc["events"]
            if e["kind"] == "remesh"]
    assert dirs == ["shrink", "grow"], dirs
    budget_dirs = [e["detail"]["direction"] for e in doc["events"]
                   if e["kind"] == "budget"]
    assert budget_dirs == ["shrink", "grow"], budget_dirs
    assert kinds.count("trigger") == 2 and kinds.count("recover") == 2
    # merged counters really are the pod sum: host tenants are disjoint
    # here, so every part tenant must appear in the merged doc
    assert {"default", "steady", "burst"} <= set(doc["tenants"])
    print(json.dumps({"table": "dryrun", "control_plane_timeline": path,
                      "storyline": seen,
                      "slot_budget": eng.slot_budget(),
                      "migrated_bit_identical": True}))


def transport_smoke() -> None:
    """PR-7 acceptance smoke (docs/transport.md): injected wire loss is
    *non-terminal* — a windowed transfer through the go-back-N
    retransmission machine delivers bit-identically to its lossless twin,
    the retries/timeouts land in the tenant counters and the timeline's
    ``retrans_s``/``timeouts_s`` rate series, and a connection-churn
    round live-migrates a lossy shared-CQ table bit-identically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import perftest
    from repro.core.obs import CounterTimeline
    from repro.runtime.fault import WireFault

    n_msgs, msg_bytes, window = 8, 1024, 4
    mesh2 = perftest.make_mesh2()
    dp = perftest._dp("cord", emulate=True, mesh=mesh2)
    payload = np.arange(n_msgs * msg_bytes, dtype=np.uint8) \
        .reshape(n_msgs, msg_bytes)
    msgs = jnp.asarray(np.stack([payload, np.zeros_like(payload)]))
    fault = WireFault(drop_rate=0.2, corrupt_rate=0.1, seed=5)

    clean, _ = perftest.build_windowed(mesh2, dp, dp, msg_bytes, n_msgs,
                                       window)
    lossy, _ = perftest.build_windowed(mesh2, dp, dp, msg_bytes, n_msgs,
                                       window, fault=fault)
    out0, _, _ = jax.block_until_ready(clean(msgs, dp.runtime_init()))
    out1, _, rt = jax.block_until_ready(lossy(msgs, dp.runtime_init()))
    np.testing.assert_array_equal(
        np.asarray(out1)[1], np.asarray(out0)[1],
        err_msg="lossy windowed transfer is not bit-identical to lossless")
    np.testing.assert_array_equal(np.asarray(out1)[1], payload)
    rep = dp.runtime_report(rt)[dp.tenant]
    assert rep["retransmits"] > 0, rep
    assert rep["retransmits"] + rep["timeouts"] + rep["cqe_errors"] > 0

    # the fault series is a first-class timeline rate
    timeline = CounterTimeline(source="transport-smoke")
    timeline.snapshot(0, dp.runtime_report(dp.runtime_init()))
    timeline.snapshot(1, dp.runtime_report(rt))
    rates = timeline.rates()[dp.tenant]
    assert rates["retrans_s"][-1] > 0, rates
    path = timeline.save("runs/transport_timeline.json")
    CounterTimeline.load(path)                    # schema validation

    # mini churn: lossy tables created → migrated mid-transfer → torn
    # down (the ≥100-QP sweep is perftest --dry-run's churn_dryrun table)
    (row,) = perftest.connection_churn(mesh2, rounds=2, qps=8,
                                       msg_bytes=64, emulate=False,
                                       table="churn_smoke")
    assert row["bit_identical"] and row["qps_churned"] == 16, row
    print(json.dumps({"table": "dryrun",
                      "lossy_vs_lossless": "bit-identical",
                      "retransmits": rep["retransmits"],
                      "timeouts": rep["timeouts"],
                      "cqe_errors": rep["cqe_errors"],
                      "retrans_s_last": round(rates["retrans_s"][-1], 2),
                      "transport_timeline": path}))
    print(json.dumps(row))


def bounce_smoke() -> None:
    """PR-6 acceptance smoke (docs/kernels.md): the Pallas dataplane
    kernels are bit-identical to the XLA emulation they replace — the
    double-buffered ``bounce_copy`` against ``staged_copy`` on a ragged
    payload (padded to whole chunks outside the kernel), and
    ``mediated_cost`` must leave the payload untouched while its SMEM
    cost totals account at least the requested delay iterations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.techniques import staged_copy
    from repro.kernels.dataplane import (COST_COPIES, COST_ITERS,
                                         bounce_copy, mediated_cost)

    x = jax.random.normal(jax.random.PRNGKey(6), (3, 1237), jnp.float32)
    for copies in (1, 3):
        np.testing.assert_array_equal(
            np.asarray(bounce_copy(x, copies=copies, chunk_elems=1024)),
            np.asarray(staged_copy(x, copies=copies)))
    out, ctrs = mediated_cost(x, delay_iters=500, copies=2,
                              chunk_elems=1024)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    ctrs = np.asarray(ctrs)
    assert int(ctrs[:, COST_ITERS].sum()) >= 500, ctrs
    assert (ctrs[:, COST_COPIES] == 2).all(), ctrs
    print(json.dumps({"table": "dryrun", "bounce_bit_identical": True,
                      "cost_chunks": int(ctrs.shape[0]),
                      "cost_iters": int(ctrs[:, COST_ITERS].sum())}))


def main() -> None:
    if "--transport-smoke" in sys.argv:
        # the PR-7 acceptance gate, runnable standalone (ci.yml step):
        # wire loss must be non-terminal and bit-identical on delivery
        transport_smoke()
        print("transport smoke ok")
        return
    if "--control-plane-smoke" in sys.argv:
        # the PR-10 acceptance gate, runnable standalone (the ci.yml
        # control-plane lane): the multi-process-mesh shrink→recover→grow
        # cycle must close with bit-identical transfers and a validated
        # merged pod artifact
        control_plane_smoke()
        print("control-plane smoke ok")
        return
    if "--dry-run" in sys.argv:
        dry_run()
        return
    fast = "--fast" in sys.argv
    rows = []

    print("# perftest (figs 1, 3, 4, 5)")
    from benchmarks import perftest
    rows += perftest.run_all(fast=fast)

    print("# NPB (fig 6)")
    from benchmarks import npb
    rows += npb.run_all()

    print("# converged (train + serve on one dataplane)")
    from benchmarks import converged
    rows += converged.run_all(fast=fast)

    print("# kernels")
    from benchmarks import kernels_bench
    rows += kernels_bench.run_all()

    if os.path.isdir("runs/dryrun") and os.listdir("runs/dryrun"):
        print("# roofline (from dry-run artifacts)")
        from benchmarks import roofline
        roof = roofline.run_all(use_hlo=not fast)
        rows += [{"table": "roofline", **r} for r in roof]

    os.makedirs("runs", exist_ok=True)
    with open("runs/bench_results.json", "w") as f:
        json.dump(rows, f, indent=1, default=str)

    # CSV summary: name,us_per_call,derived
    print("name,us_per_call,derived")
    for r in rows:
        tab = r.get("table", "?")
        if tab == "fig1":
            print(f"fig1/{r['variant']}/{r['bytes']}B,{r['latency_us']},"
                  f"gbps={r['gbps']}")
        elif tab in ("fig3", "fig5_lat"):
            print(f"{tab}/{r['transport']}/{r['op']}/{r['client']}-"
                  f"{r['server']},{r['latency_us']},"
                  f"overhead_us={r['overhead_us']}")
        elif tab in ("fig4", "fig5_bw"):
            print(f"{tab}/{r['transport']}/{r['op']}/{r['bytes']}B,,"
                  f"rel_tput={r['rel_throughput']}")
        elif tab == "window":
            print(f"window/{r['transport']}/{r['op']}/{r['bytes']}B/"
                  f"w{r['window']},,gbps={r['gbps']} cq={r['cq_hwm']}")
        elif tab == "credits":
            print(f"credits/{r['bytes']}B/w{r['window']}/"
                  f"c{r['rx_credits']},,gbps={r['gbps']} "
                  f"stalls={r['stalls']}")
        elif tab == "churn":
            print(f"churn/{r['qps_churned']}qp/"
                  f"drop{r['drop_rate']},,retrans={r['retransmits']} "
                  f"timeouts={r['timeouts']} "
                  f"bit_identical={r['bit_identical']}")
        elif tab == "converged":
            served = sum(r["served_tokens"].values())
            print(f"converged/throttle={r['throttle_train']},,"
                  f"train_wall_s={r['train_wall_s']} "
                  f"served_tokens={served} "
                  f"train_throttled={r['train_throttled']}")
        elif tab == "fig6":
            print(f"fig6/{r['bench']}/{r['mode']},{r['ms'] * 1e3},"
                  f"rel={r['rel_runtime']}")
        elif tab == "kernels":
            us = r.get("xla_flash_us") or r.get("xla_ref_us") or ""
            print(f"kernels/{r['name']},{us},"
                  f"err={r['pallas_vs_ref_err']:.2e}")
        elif tab == "roofline" and "dominant" in r:
            print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},,"
                  f"dom={r['dominant']},frac={r['roofline_fraction']:.3f}")


if __name__ == "__main__":
    main()
