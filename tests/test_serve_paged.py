"""Paged-KV serving: block-pool round-trips, gather-decode bit-identity
with the stripe layout, chunked-prefill equivalence, preemption with
exact temperature-0 resume, and submit-time admission under paging."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_model_config
from repro.configs.base import ServeConfig
from repro.core import telemetry as tl
from repro.layers.kvcache import (
    BlockAllocator,
    kv_cache_init,
    kv_pool_gather,
    kv_pool_init,
    kv_pool_insert,
    kv_pool_scatter_token,
)
from repro.models import build_model
from repro.serve import Engine, Request, ServeError

RNG = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_model_config("gemma3-1b", smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    return cfg, model, params


def _requests(lengths, tenants=None, max_new=8):
    tenants = tenants or ["default"] * len(lengths)
    return [Request(rid=i, prompt=np.asarray((np.arange(n) + 3 * i) % 100,
                                             np.int32),
                    tenant=t, max_new_tokens=max_new)
            for i, (n, t) in enumerate(zip(lengths, tenants))]


def _tokens(done):
    return {r.rid: r.out_tokens for r in done}


# ---------------------------------------------------------------------------
# block pool primitives
# ---------------------------------------------------------------------------

def test_block_allocator_round_trip():
    a = BlockAllocator(4)
    ids = a.alloc(3)
    assert ids == [1, 2, 3] and a.free_blocks == 1
    assert a.alloc(2) is None and a.free_blocks == 1   # all-or-nothing
    a.free([2])
    assert sorted(a.alloc(2)) == [2, 4]
    assert a.alloc(0) == [] and a.free_blocks == 0
    a.free([1, 2, 3, 4])
    assert a.free_blocks == 4


def test_block_allocator_double_free_raises():
    a = BlockAllocator(2)
    a.alloc(1)
    a.free([1])
    with pytest.raises(ValueError, match="double free"):
        a.free([1])
    with pytest.raises(ValueError, match="double free"):
        a.free([0])                      # the null block is never handed out


def test_kv_pool_insert_then_gather_bitwise():
    L, bs, KVH, hd = 2, 4, 1, 3
    pool = kv_pool_init(L, 6, bs, KVH, hd, dtype=jnp.float32)
    pre = {k: v + 7.0 for k, v in
           kv_cache_init(L, 1, 8, KVH, hd, dtype=jnp.float32).items()}
    pool = kv_pool_insert(pool, pre, jnp.asarray([2, 5], jnp.int32), bs)
    dense = kv_pool_gather(pool, jnp.asarray([[2, 5, 0]], jnp.int32), bs)
    assert dense["k"].shape == (L, 1, 12, KVH * hd)
    np.testing.assert_array_equal(np.asarray(dense["k"][:, 0, :8]), 7.0)
    # the unallocated table tail reads the null block: zeros
    assert float(jnp.abs(dense["k"][:, 0, 8:]).max()) == 0.0


def test_kv_pool_scatter_token_targets_and_drops():
    L, bs, KVH, hd = 1, 4, 1, 2
    pool = kv_pool_init(L, 4, bs, KVH, hd, dtype=jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    pos = jnp.asarray([5, 1], jnp.int32)
    active = jnp.asarray([True, False])
    dense = kv_pool_gather(pool, tables, bs)
    dense = {k: v.at[:, 0, 5].set(9.0).at[:, 1, 1].set(4.0)
             for k, v in dense.items()}
    rows = jnp.arange(2)
    tok = {k: v[:, rows, pos] for k, v in dense.items()}   # (L, B, KVH·hd)
    pool = kv_pool_scatter_token(pool, tok, tables, pos, active, bs)
    # slot 0, pos 5 → physical block tables[0, 1] = 2 at offset 1
    assert float(pool["k"][0, 2, 1].max()) == 9.0
    assert float(jnp.abs(pool["k"][0, 3]).max()) == 0.0   # inactive dropped
    assert float(jnp.abs(pool["k"][0, 0]).max()) == 0.0   # null block intact


# ---------------------------------------------------------------------------
# ServeConfig validation
# ---------------------------------------------------------------------------

def test_serve_config_paged_validation():
    with pytest.raises(ValueError, match="block_size"):
        ServeConfig(block_size=-1)
    with pytest.raises(ValueError, match="divide"):
        ServeConfig(block_size=6, kv_cache_len=64)
    with pytest.raises(ValueError, match="n_blocks"):
        ServeConfig(n_blocks=-1)
    with pytest.raises(ValueError, match="requires block_size"):
        ServeConfig(n_blocks=4)
    with pytest.raises(ValueError, match="power of two"):
        ServeConfig(prefill_chunk=12)
    with pytest.raises(ValueError, match="power of two"):
        ServeConfig(prefill_chunk=4)
    with pytest.raises(ValueError, match="multiple"):
        ServeConfig(prefill_chunk=16, block_size=32, kv_cache_len=64)
    sc = ServeConfig(block_size=8, n_blocks=4, prefill_chunk=16,
                     kv_cache_len=64)
    assert sc.block_size == 8 and sc.n_blocks == 4


# ---------------------------------------------------------------------------
# paged engine: bit-identity, admission, preemption, chunked prefill
# ---------------------------------------------------------------------------

def test_paged_decode_matches_stripe_bitwise(smoke_model):
    """Gather → fixed-shape decode → scatter over the block pool emits the
    exact tokens the contiguous stripe layout emits at temperature 0, on a
    mixed-length stream, with the decode step still compiled once."""
    cfg, model, params = smoke_model
    base = dict(max_batch=2, max_new_tokens=5, kv_cache_len=64)
    stripe = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    paged = Engine(model, params, cfg, ServeConfig(**base, block_size=8),
                   eos_id=-1)
    assert paged.paged and not stripe.paged
    lengths = [8, 13, 21, 8, 30]
    out_s = _tokens(stripe.run(_requests(lengths, max_new=5)))
    out_p = _tokens(paged.run(_requests(lengths, max_new=5)))
    assert out_p == out_s
    assert paged.decode_compile_count() == 1


def test_paged_admits_prompt_longer_than_stripe(smoke_model):
    """Slot count decouples from context length: a prompt no fixed stripe
    can hold is admissible while free blocks exist; the stripe layout
    rejects it with a clear submit-time ServeError."""
    cfg, model, params = smoke_model
    base = dict(max_batch=2, max_new_tokens=8, kv_cache_len=56)
    stripe = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    with pytest.raises(ServeError, match="cache positions"):
        stripe.run(_requests([80]))
    paged = Engine(model, params, cfg,
                   ServeConfig(**base, block_size=8, n_blocks=24), eos_id=-1)
    (done,) = paged.run(_requests([80]))
    assert done.done and len(done.out_tokens) == 8
    # a prompt the POOL cannot ever hold still fails loudly at submit
    tiny = Engine(model, params, cfg,
                  ServeConfig(**base, block_size=8, n_blocks=4), eos_id=-1)
    with pytest.raises(ServeError, match="pool blocks"):
        tiny.run(_requests([80]))


def test_pool_pressure_preempts_and_resumes_exact(smoke_model):
    """Under a pool too small for both residents' growth, the engine
    preempts (tokens = snapshot, blocks freed, request re-queued) and the
    resumed request finishes with exactly the tokens of an unpressured
    run — recompute is exact at temperature 0."""
    cfg, model, params = smoke_model
    base = dict(max_batch=2, max_new_tokens=8, kv_cache_len=64,
                block_size=8)
    roomy = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    # each request needs 2 blocks (16 positions); 3 can't host both
    tight = Engine(model, params, cfg, ServeConfig(**base, n_blocks=3),
                   eos_id=-1)
    out_r = _tokens(roomy.run(_requests([8, 8])))
    out_t = _tokens(tight.run(_requests([8, 8])))
    assert out_t == out_r
    rep = tight.tenant_report()["default"]
    assert rep["preemptions"] >= 1 and rep["restores"] >= 1
    ctrs, names = tight.runtime_counters()
    i = list(names).index("default")
    assert ctrs[i, tl.CTR_PREEMPTIONS] == rep["preemptions"]
    assert ctrs[i, tl.CTR_RESTORES] == rep["restores"]
    assert tight._alloc.free_blocks == 3       # every block returned


def test_preemption_lands_in_saved_timeline(smoke_model, tmp_path):
    """Preempt/restore is visible in a saved engine timeline: a 9-block
    pool fits one request's whole lifetime (the submit bound) but not two
    co-residents' decode growth (5 blocks each by the end), and the
    artifact carries the cumulative counters, the ``preempt_s`` /
    ``restore_s`` rates and the ``free_blocks`` gauge."""
    from repro.core.obs import CounterTimeline

    cfg, model, params = smoke_model
    timeline = CounterTimeline(source="test-serve/preempt")
    eng = Engine(model, params, cfg,
                 ServeConfig(max_batch=2, max_new_tokens=32, kv_cache_len=64,
                             block_size=8, n_blocks=9),
                 eos_id=-1, obs=timeline)
    done = eng.run(_requests([8, 8], max_new=32))
    assert all(len(r.out_tokens) == 32 for r in done)
    rep = eng.tenant_report()["default"]
    assert rep["preemptions"] > 0 and rep["restores"] > 0, rep
    doc = CounterTimeline.load(
        timeline.save(str(tmp_path / "serve_timeline.json")))
    assert doc["samples"], "engine timeline captured no ticks"
    last = doc["samples"][-1]
    assert last["tenants"]["default"]["preemptions"] == rep["preemptions"]
    assert last["tenants"]["default"]["restores"] == rep["restores"]
    assert {"preempt_s", "restore_s"} <= set(doc["rate_fields"])
    assert "free_blocks" in last["gauges"]


def test_slot_budget_preempts_mid_run_exact(smoke_model):
    """set_slot_budget mid-decode evicts over-budget slots; the evicted
    requests resume (serially, under the tightened cap) with bit-identical
    tokens — WFQ budgets are enforceable, not advisory."""
    cfg, model, params = smoke_model
    sc = ServeConfig(max_batch=4, max_new_tokens=6, kv_cache_len=64,
                     block_size=8)
    ref = Engine(model, params, cfg, sc, eos_id=-1)
    out_ref = _tokens(ref.run(_requests([8] * 4, max_new=6)))
    eng = Engine(model, params, cfg, sc, eos_id=-1)
    calls = {"n": 0}
    orig = eng._step_pool

    def spy(*a):
        calls["n"] += 1
        if calls["n"] == 2:
            eng.set_slot_budget(1)       # tighten while 4 slots are held
        return orig(*a)

    eng._step_pool = spy
    out = _tokens(eng.run(_requests([8] * 4, max_new=6)))
    assert out == out_ref
    rep = eng.tenant_report()["default"]
    assert rep["preemptions"] >= 3 and rep["restores"] >= 3
    eng.set_slot_budget(0)               # relax back to the config cap
    assert eng._budget_cap == 0


def test_chunked_prefill_matches_whole_prefill(smoke_model):
    """Chunk-at-a-time prefill (interleaved with decode ticks) emits the
    same tokens as whole-prompt prefill, in both stripe and paged
    layouts."""
    cfg, model, params = smoke_model
    base = dict(max_batch=2, max_new_tokens=4, kv_cache_len=128)
    lengths = [40, 8, 23]
    whole = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    out_w = _tokens(whole.run(_requests(lengths, max_new=4)))
    chunked = Engine(model, params, cfg,
                     ServeConfig(**base, prefill_chunk=16), eos_id=-1)
    assert chunked.chunked
    assert _tokens(chunked.run(_requests(lengths, max_new=4))) == out_w
    both = Engine(model, params, cfg,
                  ServeConfig(**base, prefill_chunk=16, block_size=8),
                  eos_id=-1)
    assert both.paged and both.chunked
    assert _tokens(both.run(_requests(lengths, max_new=4))) == out_w


def _count_chunks(eng):
    """Count traced chunk-prefill steps on ``eng`` (replay detector)."""
    orig, c = eng._chunk, {"n": 0}

    def wrapped(*a, **kw):
        c["n"] += 1
        return orig(*a, **kw)

    eng._chunk = wrapped
    return c


def test_preempt_mid_chunked_prefill_replays_pending_chunks(smoke_model):
    """Regression: preempting a slot whose chunked prefill is still
    PENDING must drop the partial prefill state (``_prefills`` entry and
    queue position) and replay every chunk from offset 0 on resume — the
    emitted-tokens snapshot holds nothing for a request that never
    activated, so a stale entry or a skipped chunk would silently corrupt
    whatever lands in that slot next."""
    cfg, model, params = smoke_model
    base = dict(max_batch=2, max_new_tokens=6, kv_cache_len=128,
                prefill_chunk=16, block_size=8)
    lengths = [8, 40]                    # rid 1 prefills over >= 3 chunks

    ref = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    c_ref = _count_chunks(ref)
    out_ref = _tokens(ref.run(_requests(lengths, max_new=6)))

    eng = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    c_eng = _count_chunks(eng)
    orig_adv = eng._advance_chunk

    def adv(*a):
        out = orig_adv(*a)
        if c_eng["n"] == 1:              # first chunk landed; rest pending
            eng.set_slot_budget(1)       # next tick preempts the new slot
        return out

    eng._advance_chunk = adv
    out = _tokens(eng.run(_requests(lengths, max_new=6)))
    assert out == out_ref
    assert eng.tenant_report()["default"]["preemptions"] >= 1
    assert c_eng["n"] > c_ref["n"]       # the pending chunks were REPLAYED
    assert not eng._prefills             # no stale chunk state survives
    assert eng._alloc.free_blocks == eng._n_usable


def test_pool_pressure_while_chunked_prefill_pending(smoke_model):
    """Pool pressure striking while another slot's chunked prefill is in
    flight: the prefilling slot claimed its blocks up-front and is not a
    pressure victim, so the decoding slot preempts ITSELF, waits out the
    prefill, and resumes — both requests finish with the unpressured
    run's exact tokens and every block returns to the pool."""
    cfg, model, params = smoke_model
    base = dict(max_batch=2, max_new_tokens=6, kv_cache_len=128,
                prefill_chunk=16, block_size=8)
    lengths = [8, 40]
    roomy = Engine(model, params, cfg, ServeConfig(**base), eos_id=-1)
    out_r = _tokens(roomy.run(_requests(lengths, max_new=6)))
    # pool: rid 1's up-front prefill claim + one block — rid 0's first
    # decode growth past its initial block finds the free list empty
    need = -(-roomy._cover(lengths[1]) // base["block_size"])
    tight = Engine(model, params, cfg,
                   ServeConfig(**base, n_blocks=need + 1), eos_id=-1)
    out_t = _tokens(tight.run(_requests(lengths, max_new=6)))
    assert out_t == out_r
    assert tight.tenant_report()["default"]["preemptions"] >= 1
    assert tight._alloc.free_blocks == need + 1


def test_prefill_chunk_logits_and_cache_bitwise(smoke_model):
    """Model-level: scanning chunks at traced offsets reproduces the whole
    prefill's final-position logits and KV cache bit-for-bit."""
    cfg, model, params = smoke_model
    toks = jnp.asarray((np.arange(32) % 97)[None, :], jnp.int32)
    last = jnp.asarray([31], jnp.int32)
    logits_w, cache_w = model.prefill(params, {"tokens": toks},
                                      model.init_cache(1, 32), last_pos=last)
    cache_c = model.init_cache(1, 32)
    C = 8
    for off in range(0, 32, C):
        logits_c, cache_c = model.prefill_chunk(
            params, {"tokens": toks[:, off:off + C]}, cache_c,
            jnp.int32(off), last_pos=last)
    np.testing.assert_array_equal(np.asarray(logits_w), np.asarray(logits_c))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_w[name]),
                                      np.asarray(cache_c[name]))


@pytest.mark.parametrize("block_size", [0, 8])
def test_served_logits_match_cache_free_forward(block_size):
    """Prefill (whole and chunked) then decode through the cache gives the
    logits of a cache-free full forward over the same tokens — the
    serve-side check ``chip_smoke.py`` makes at full width on the chip,
    here at smoke size in float32 (so only summation order differs)."""
    from repro.layers.embedding import logits

    cfg = get_model_config("granite-3-2b", smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    eng = Engine(model, params, cfg,
                 ServeConfig(max_batch=2, max_new_tokens=6, kv_cache_len=64,
                             block_size=block_size, prefill_chunk=16),
                 eos_id=-1)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32),
                    max_new_tokens=6, logits=[])
            for i, n in enumerate((5, 23, 40))]        # 40 > chunk: chunked
    for r in eng.run(reqs):
        fed = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1])])
        x = model.apply(params, {"tokens": jnp.asarray(fed)[None]},
                        impl="naive")[0]
        ref = np.asarray(logits(params["embed"], x))[0]
        want = ref[len(r.prompt) - 1 + np.arange(len(r.out_tokens))]
        got = np.stack(r.logits)
        assert got.shape == want.shape == (6, cfg.vocab_size)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
