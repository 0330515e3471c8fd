"""Persistent-slot continuous batching: temperature-0 equivalence with
the batch-1 greedy reference, mid-decode slot refill, WFQ slot shares,
single-compile decode, fused-mediation bit-equivalence, and admission
accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_model_config
from repro.configs.base import DataplaneConfig, ServeConfig
from repro.core import Dataplane
from repro.core import techniques as tech
from repro.core import telemetry as tl
from repro.core.mediation import HostTokenBucket
from repro.core.policies import QoSPolicy, TelemetryPolicy
from repro.layers.kvcache import (
    kv_cache_init,
    kv_slot_insert,
    kv_update_slots,
    slot_validity,
)
from repro.models import build_model
from repro.serve import Engine, Request, WFQScheduler, prompt_bucket

RNG = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_model_config("gemma3-1b", smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    return cfg, model, params


def _requests(lengths, tenants=None, max_new=16):
    tenants = tenants or ["default"] * len(lengths)
    return [Request(rid=i, prompt=np.asarray((np.arange(n) + 3 * i) % 100,
                                             np.int32),
                    tenant=t, max_new_tokens=max_new)
            for i, (n, t) in enumerate(zip(lengths, tenants))]


# ---------------------------------------------------------------------------
# reference equivalence + slot lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_size", [0, 8])
def test_continuous_matches_reference_temp0(smoke_model, greedy_reference,
                                            block_size):
    """At temperature 0 the engine — five requests through two slots, on
    the stripe and on the block pool — emits each request's tokens of a
    plain batch-1 prefill-then-decode loop over its prompt alone."""
    cfg, model, params = smoke_model
    sc = ServeConfig(max_batch=2, max_new_tokens=5, kv_cache_len=64,
                     block_size=block_size)
    eng = Engine(model, params, cfg, sc, eos_id=-1)
    reqs = _requests([8] * 5)
    out = {r.rid: r.out_tokens for r in eng.run(reqs)}
    want = greedy_reference(model, params, [r.prompt for r in reqs], 5)
    assert out == dict(enumerate(want))
    assert all(len(o) == 5 for o in out.values())


def test_mid_decode_refill_tokens_independent_of_coresidents(smoke_model):
    """A request refilled into a freed slot mid-decode produces the same
    tokens as when served alone: co-residents (and the slot's previous
    occupant's stale cache) never leak into it."""
    cfg, model, params = smoke_model
    sc = ServeConfig(max_batch=2, max_new_tokens=8, kv_cache_len=64)
    eng = Engine(model, params, cfg, sc, eos_id=-1)
    # r0 ends after 3 tokens, freeing its slot while r1 still decodes;
    # r2 (varied length) is inserted mid-decode next to the running r1.
    crowd = _requests([8, 11, 5], max_new=8)
    crowd[0].max_new_tokens = 3
    late_in_crowd = next(r for r in eng.run(crowd) if r.rid == 2)
    alone = _requests([8, 11, 5], max_new=8)[2]
    (alone_done,) = eng.run([alone])
    assert late_in_crowd.out_tokens == alone_done.out_tokens
    assert len(late_in_crowd.out_tokens) == 8


def test_single_decode_compilation_across_varied_stream(smoke_model):
    """One engine, one decode-step compile, regardless of the request
    mix."""
    cfg, model, params = smoke_model
    lengths = [4, 9, 17, 6, 12, 20, 5, 10]      # buckets 8 / 16 / 32
    sc = ServeConfig(max_batch=2, max_new_tokens=4, kv_cache_len=64)
    cont = Engine(model, params, cfg, sc, eos_id=-1)
    cont.run(_requests(lengths))
    assert cont._step_slots._cache_size() == 1
    assert cont.decode_compile_count() == 1


def test_wfq_slot_occupancy_proportional_to_weights(smoke_model):
    """Tenant weights 3:1 under a saturated queue: decode-slot occupancy
    splits 3:1 within ±10%."""
    cfg, model, params = smoke_model
    dp = Dataplane(
        DataplaneConfig(mode="cord"), tenants=("a", "b"),
        policies=[TelemetryPolicy(),
                  QoSPolicy(rates={"a": 3.0, "b": 1.0}, burst=1000.0)])
    sc = ServeConfig(max_batch=4, max_new_tokens=6, kv_cache_len=48)
    eng = Engine(model, params, cfg, sc, dp=dp, eos_id=-1)
    lengths, tenants = [], []
    for _ in range(8):                   # 24 a-requests : 8 b-requests
        lengths += [8, 8, 8, 8]
        tenants += ["a", "a", "a", "b"]
    done = eng.run(_requests(lengths, tenants, max_new=6))
    assert len(done) == 32
    rep = eng.tenant_report()
    ratio = rep["a"]["occupancy_steps"] / rep["b"]["occupancy_steps"]
    assert abs(ratio - 3.0) <= 0.3, rep
    ctrs, names = eng.runtime_counters()
    assert set(names) == {"a", "b"}
    occ = {t: ctrs[i, tl.CTR_CHUNKS] for i, t in enumerate(names)}
    assert occ["a"] == rep["a"]["occupancy_steps"]


def test_wfq_scheduler_grant_ratio_unit():
    wfq = WFQScheduler({"a": 3.0, "b": 1.0})
    grants = {"a": 0, "b": 0}
    for _ in range(400):
        wfq.note_backlog(["a", "b"])
        t = wfq.order(["a", "b"])[0]
        grants[t] += 1
        wfq.grant(t, cost=8)
    assert abs(grants["a"] / grants["b"] - 3.0) < 0.2


def test_wfq_idle_tenant_cannot_hoard_credit():
    """Regression: a tenant that idles while another is served must
    re-enter at the current virtual clock, not at its stale virtual time
    (which would let it monopolize slots until it 'caught up')."""
    wfq = WFQScheduler({"a": 1.0, "b": 1.0})
    wfq.note_backlog(["a", "b"])
    wfq.grant("b", cost=8)               # b served once, then goes idle
    for _ in range(100):                 # a alone backlogged
        wfq.note_backlog(["a"])
        wfq.grant("a", cost=8)
    grants = {"a": 0, "b": 0}
    for _ in range(20):                  # b returns
        wfq.note_backlog(["a", "b"])
        t = wfq.order(["a", "b"])[0]
        grants[t] += 1
        wfq.grant(t, cost=8)
    # equal weights → roughly alternating service, not a b-monopoly
    assert grants["b"] <= 11, grants


def test_max_slots_per_tenant_caps_occupancy(smoke_model):
    cfg, model, params = smoke_model
    sc = ServeConfig(max_batch=4, max_new_tokens=4, kv_cache_len=48,
                     max_slots_per_tenant=1)
    eng = Engine(model, params, cfg, sc, eos_id=-1)
    done = eng.run(_requests([8] * 6, ["hog"] * 5 + ["other"]))
    assert len(done) == 6
    rep = eng.tenant_report()
    # 5 hog requests × 3 decode steps each, never more than 1 slot at a
    # time: occupancy equals serial service, not parallel
    assert rep["hog"]["occupancy_steps"] == 15
    assert rep["hog"]["wfq_grants"] == 5


# ---------------------------------------------------------------------------
# admission accounting (satellite regressions)
# ---------------------------------------------------------------------------

def _bucket_engine(smoke_model, rates, burst=1.0, max_batch=1, scale=4.0):
    cfg, model, params = smoke_model
    dp = Dataplane(DataplaneConfig(mode="cord"),
                   tenants=tuple(["default"] + list(rates)),
                   policies=[TelemetryPolicy(),
                             QoSPolicy(rates=rates, burst=burst)])
    return Engine(model, params, cfg,
                  ServeConfig(max_batch=max_batch, max_new_tokens=2,
                              kv_cache_len=64, admission_token_scale=scale),
                  dp=dp, eos_id=-1)


def test_admission_charges_prompt_tokens(smoke_model):
    """The host bucket debits len(prompt) per admission (scaled bucket),
    matching the traced bucket's byte-proportional debits."""
    eng = _bucket_engine(smoke_model, {"t": 1.0}, burst=4.0, max_batch=4,
                         scale=4.0)
    bucket = eng._buckets["t"]
    assert bucket.burst == 16.0 and bucket.rate == 4.0   # scaled by 4
    r6 = Request(rid=0, prompt=np.arange(6, dtype=np.int32), tenant="t",
                 max_new_tokens=2)
    (done,) = eng.run([r6])
    assert done is r6 and len(r6.out_tokens) == 2
    # one scheduling round: refill capped at burst, then -6
    assert bucket.tokens == 16.0 - 6.0
    assert bucket.can_take(10.0) and not bucket.can_take(10.1)


def test_admission_cost_clamped_to_burst(smoke_model):
    """A prompt longer than the bucket can ever hold drains a full bucket
    instead of being permanently inadmissible (no 10k-round starvation
    spin)."""
    eng = _bucket_engine(smoke_model, {"t": 1.0}, burst=1.0, max_batch=2,
                         scale=4.0)
    big = Request(rid=0, prompt=np.arange(20, dtype=np.int32), tenant="t",
                  max_new_tokens=2)
    (done,) = eng.run([big])
    assert done is big and len(big.out_tokens) == 2
    assert eng._buckets["t"].tokens == 0.0       # burst 4 fully drained
    assert eng.tenant_stats["t"]["deferrals"] == 0


def test_continuous_counts_deferrals_behind_occupied_slots(smoke_model):
    """A bucket-starved tenant waiting while every slot is occupied still
    accrues deferrals: starvation is counted per scheduling round, not
    only when a slot is free."""
    cfg, model, params = smoke_model
    dp = Dataplane(
        DataplaneConfig(mode="cord"), tenants=("default", "slow"),
        policies=[TelemetryPolicy(),
                  QoSPolicy(rates={"slow": 0.05}, burst=0.25)])
    sc = ServeConfig(max_batch=1, max_new_tokens=8, kv_cache_len=32,
                     admission_token_scale=4.0)   # slow: rate .2, burst 1
    eng = Engine(model, params, cfg, sc, dp=dp, eos_id=-1)
    reqs = _requests([8, 8, 8], ["slow", "default", "slow"], max_new=8)
    reqs[0].max_new_tokens = 2           # drains the slow bucket, exits fast
    done = eng.run(reqs)
    assert len(done) == 3 and all(r.done for r in done)
    # while "default" held the only slot, "slow" sat bucket-starved and
    # was deferred each scheduling round, not just when a slot was free
    assert eng.tenant_report()["slow"]["deferrals"] >= 2


def test_slot_report_live_view(smoke_model):
    """slot_report exposes the per-slot pos/active/tenant vectors while a
    run is in flight (the serve-side dashboard feed)."""
    cfg, model, params = smoke_model
    sc = ServeConfig(max_batch=2, max_new_tokens=4, kv_cache_len=32)
    eng = Engine(model, params, cfg, sc, eos_id=-1)
    seen = []
    orig = eng._step_slots
    def spy(*a):
        seen.append(eng.slot_report())
        return orig(*a)
    eng._step_slots = spy
    eng.run(_requests([8, 8, 8], ["a", "b", "a"], max_new=4))
    mid = seen[0]
    assert {s["tenant"] for s in mid if s["active"]} == {"a", "b"}
    assert all(s["pos"] == 8 for s in mid if s["active"])
    assert not any(s["active"] for s in eng.slot_report())   # drained


def test_duplicate_rids_and_prompts_are_servable(smoke_model):
    """Regression: requests are tracked by identity — duplicate rids (and
    equal-content prompts) must not confuse queue removal (ndarray ==
    inside dataclass equality used to raise mid-serve)."""
    cfg, model, params = smoke_model
    sc = ServeConfig(max_batch=2, max_new_tokens=3, kv_cache_len=32)
    eng = Engine(model, params, cfg, sc, eos_id=-1)
    dup = [Request(rid=0, prompt=np.arange(8, dtype=np.int32), tenant="b"),
           Request(rid=0, prompt=np.arange(5, dtype=np.int32), tenant="a"),
           Request(rid=0, prompt=np.arange(8, dtype=np.int32), tenant="a")]
    done = eng.run(dup)
    assert len(done) == 3 and all(r.done for r in done)
    assert all(len(r.out_tokens) == 3 for r in done)


def test_host_bucket_from_policy_scaling():
    buckets = HostTokenBucket.from_policy(
        QoSPolicy(rates={"a": 0.5}, burst=2.0), scale=8.0)
    assert buckets["a"].rate == 4.0 and buckets["a"].burst == 16.0


# ---------------------------------------------------------------------------
# slot-aware kvcache helpers
# ---------------------------------------------------------------------------

def test_kv_slot_insert_and_update_slots():
    cache = kv_cache_init(2, 3, 16, 1, 4, dtype=jnp.float32)
    pre = {k: v + 7.0 for k, v in
           kv_cache_init(2, 1, 8, 1, 4, dtype=jnp.float32).items()}
    cache = kv_slot_insert(cache, pre, jnp.int32(1))
    assert float(cache["k"][:, 1, :8].min()) == 7.0
    assert float(jnp.abs(cache["k"][:, 0]).max()) == 0.0   # other slots kept
    assert float(jnp.abs(cache["k"][:, 1, 8:]).max()) == 0.0

    ck, cv = cache["k"][0], cache["v"][0]                  # one layer (3,16,1,4)
    k_new = jnp.full((3, 1, 1, 4), 9.0)
    pos = jnp.asarray([0, 5, 15], jnp.int32)
    ck2, _ = kv_update_slots(ck, cv, k_new, k_new, pos)
    for row, p in enumerate([0, 5, 15]):
        assert float(ck2[row, p].min()) == 9.0
    np.testing.assert_array_equal(
        np.asarray(slot_validity(6, jnp.asarray([0, 3]))),
        [[1, 0, 0, 0, 0, 0], [1, 1, 1, 1, 0, 0]])


def test_prompt_bucket_powers_of_two():
    assert [prompt_bucket(n) for n in (1, 8, 9, 16, 17, 100)] == \
        [8, 8, 16, 16, 32, 128]


# ---------------------------------------------------------------------------
# fused mediation costs
# ---------------------------------------------------------------------------

def _pipeline_roundtrip(dp, x):
    rec = tl.OpRecord(kind="all_reduce", tag="fuse/test", bytes=tl.nbytes(x),
                      axes=("data",), mode=dp.mode)

    def f(v, rt):
        v, rt = dp.pipeline.send(v, rec, rt, 0)
        v, rt = dp.pipeline.complete(v, rec, rt, 0)
        return v, rt

    out, rt = jax.jit(f)(x, dp.runtime_init())
    return np.asarray(out), np.asarray(rt["counters"])


@pytest.mark.parametrize("mode", ["bypass", "cord", "socket"])
def test_fused_pipeline_bit_identical_per_stage(mode):
    """Fused cost emission (one delay chain + one copy pass per side) is
    bit-identical to the per-stage pipeline in every mode preset, runtime
    counters included."""
    x = jax.random.normal(RNG, (128,))
    outs, ctrs = {}, {}
    for fused in (True, False):
        dp = Dataplane(DataplaneConfig(mode=mode, emulate_costs=True,
                                       fuse_mediation=fused))
        assert dp.pipeline.fused is fused
        outs[fused], ctrs[fused] = _pipeline_roundtrip(dp, x)
    np.testing.assert_array_equal(outs[True], outs[False])
    np.testing.assert_array_equal(ctrs[True], ctrs[False])


def test_fused_pipeline_emits_single_delay_chain(monkeypatch):
    """socket mode per-stage pays one delay_chain per cost stage; the
    fused pipeline emits ≤ 1 per side."""
    calls = {"n": 0}
    orig = tech.delay_chain

    def counting(x, iters):
        calls["n"] += 1
        return orig(x, iters)

    monkeypatch.setattr(tech, "delay_chain", counting)
    x = jnp.ones(16)
    rec = tl.OpRecord(kind="all_reduce", tag="fuse/count", bytes=64,
                      axes=("data",))
    counts = {}
    for fused in (True, False):
        dp = Dataplane(DataplaneConfig(mode="socket", emulate_costs=True,
                                       fuse_mediation=fused))
        per_side = {}
        for side in ("send", "complete"):
            calls["n"] = 0
            getattr(dp.pipeline, side)(x, rec, dp.runtime_init(), 0)
            per_side[side] = calls["n"]
        counts[fused] = per_side
    assert counts[False]["send"] == 2          # syscall + socket-stack
    assert counts[True]["send"] == 1           # fused into one chain
    assert counts[True]["complete"] <= 1
    # total serial cost is preserved by fusion
    for side in ("send_delay_iters", "complete_delay_iters"):
        a = getattr(Dataplane(DataplaneConfig(mode="socket",
                                              emulate_costs=True)).pipeline,
                    side)(rec)
        b = getattr(Dataplane(DataplaneConfig(mode="socket",
                                              emulate_costs=True,
                                              fuse_mediation=False)).pipeline,
                    side)(rec)
        assert a == b
