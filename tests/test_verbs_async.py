"""The CQ-driven async verbs runtime: sender-window bounds, credit
flow control (stall + resume), windowed/synchronous bit-equivalence,
per-tenant runtime accounting of verbs traffic — plus regression tests
for the READ phantom-completion, first-token-EOS and msg_bytes
truncation bugs."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import DataplaneConfig, ModelConfig, ServeConfig
from repro.core import Dataplane, compat, verbs


def _dp(mode, mesh, **kw):
    return Dataplane(DataplaneConfig(mode=mode, emulate_costs=True, **kw),
                     mesh=mesh)


def _run_windowed(mesh, dp, cfg, payload, *, credits, op="send",
                  with_state=True):
    """One windowed transfer src=0→dst=1; returns (out rows, qp scalars,
    per-tenant report or None)."""
    n = payload.shape[0]
    msgs = jnp.asarray(np.stack([payload, np.zeros_like(payload)]))

    @partial(compat.shard_map, mesh=mesh,
             in_specs=(P("rank", None, None), P()),
             out_specs=(P("rank", None, None), (P(), P(), P(), P()), P()))
    def f(m, rt):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(cfg)
        if op == "send":
            qp, rt = verbs.post_recv(dp, cfg, qp, rank, dst=1, n=credits,
                                     state=rt)
        out, qp, rt = verbs.windowed_send(dp, cfg, qp, m[0], rank, src=0,
                                          dst=1, op=op, state=rt)
        rt = verbs.allreduce_state(rt)
        return (out[None], (qp["win_hwm"], qp["cq_hwm"], qp["cq_sent"],
                            qp["credits"]), rt)

    rt0 = dp.runtime_init() if with_state else None
    out, scalars, rt = jax.jit(f)(msgs, rt0)
    report = dp.runtime_report(rt)[dp.tenant] if with_state else None
    return np.asarray(out), [int(s) for s in scalars], report


def _payload(n, msg_bytes, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(n, msg_bytes), dtype=np.uint8)


# ---------------------------------------------------------------------------
# sender window
# ---------------------------------------------------------------------------

def test_window_never_exceeds_max_outstanding(mesh2):
    dp = _dp("cord", mesh2)
    for w in (1, 2, 4):
        cfg = verbs.QPConfig(transport="RC", msg_bytes=32, depth=8,
                             max_outstanding=w)
        out, (win_hwm, cq_hwm, cq_sent, _), _ = _run_windowed(
            mesh2, dp, cfg, _payload(10, 32), credits=10)
        assert win_hwm == w          # the window fills exactly to the cap
        assert cq_hwm <= cfg.effective_cq_depth
        assert cq_sent == 10         # every WR eventually completed


def test_windowed_report_counts_verbs_traffic(mesh2):
    """Verbs ops land in dp.runtime_report: ops/bytes from the pipeline's
    counter-bump, completions/credits/cq_depth from the CQ runtime."""
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="RC", msg_bytes=64, depth=8,
                         max_outstanding=4)
    n = 8
    _, _, rep = _run_windowed(mesh2, dp, cfg, _payload(n, 64), credits=n)
    assert rep["ops"] == n + 1             # n posts + 1 post_recv
    assert rep["bytes"] == n * 64 + 4      # payloads + credit-grant token
    assert rep["completions"] == n
    assert rep["credits"] == n
    assert rep["stalls"] == 0
    assert rep["cq_depth"] == 4            # CQ high-water = the window


# ---------------------------------------------------------------------------
# credit flow control
# ---------------------------------------------------------------------------

def test_credit_exhaustion_stalls_then_resumes(mesh2):
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="RC", msg_bytes=32, depth=8,
                         max_outstanding=8)
    n, credits = 12, 3
    payload = _payload(n, 32)
    out, (_, _, cq_sent, left), rep = _run_windowed(
        mesh2, dp, cfg, payload, credits=credits)
    # the sender ran dry every `credits` sends and resumed after each
    # receiver re-post: ceil(n/credits) - 1 stall episodes
    assert rep["stalls"] == (n + credits - 1) // credits - 1 == 3
    assert rep["credits"] == n             # every send consumed one credit
    assert cq_sent == n                    # ...and still completed them all
    np.testing.assert_array_equal(out[1], payload)   # delivery intact
    # ample credits: no stalls at all
    _, _, rep2 = _run_windowed(mesh2, dp, cfg, payload, credits=n)
    assert rep2["stalls"] == 0 and rep2["credits"] == n


def test_one_sided_ops_bypass_credits(mesh2):
    """WRITE consumes no receiver credits (no recv queue involvement)."""
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="RC", msg_bytes=32, depth=8,
                         max_outstanding=2)
    payload = _payload(6, 32)
    out, (_, _, cq_sent, credits_left), rep = _run_windowed(
        mesh2, dp, cfg, payload, credits=0, op="write")
    assert cq_sent == 6 and credits_left == 0
    assert rep["credits"] == 0 and rep["stalls"] == 0
    np.testing.assert_array_equal(out[1], payload)


# ---------------------------------------------------------------------------
# windowed ≡ synchronous, per mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["bypass", "cord", "socket"])
def test_windowed_bit_identical_to_sync_path(mesh2, mode):
    dp = _dp(mode, mesh2)
    n, msg_bytes = 6, 64
    payload = _payload(n, msg_bytes, seed=3)
    cfg_w = verbs.QPConfig(transport="RC", msg_bytes=msg_bytes, depth=4,
                           max_outstanding=2)
    out, _, _ = _run_windowed(mesh2, dp, cfg_w, payload, credits=n,
                              with_state=False)

    cfg_s = verbs.QPConfig(transport="RC", msg_bytes=msg_bytes, depth=n)

    @partial(compat.shard_map, mesh=mesh2, in_specs=P("rank", None, None),
             out_specs=P("rank", None, None))
    def sync(m):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(cfg_s)
        for i in range(n):
            qp, _ = verbs.post_send(dp, cfg_s, qp, m[0, i], rank, src=0)
        qp, _ = verbs.flush_send(dp, cfg_s, qp, rank, src=0, dst=1)
        return qp["recv_ring"][None]

    ring = jax.jit(sync)(
        jnp.asarray(np.stack([payload, np.zeros_like(payload)])))
    np.testing.assert_array_equal(out[1], np.asarray(ring)[1][:n])
    np.testing.assert_array_equal(out[1], payload)


def test_windowed_ud_delivery(mesh2):
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="UD", msg_bytes=128, depth=4,
                         max_outstanding=4)
    payload = _payload(5, 128, seed=5)
    out, (_, _, cq_sent, _), _ = _run_windowed(mesh2, dp, cfg, payload,
                                               credits=5)
    assert cq_sent == 5
    np.testing.assert_array_equal(out[1], payload)


# ---------------------------------------------------------------------------
# CQ ring mechanics
# ---------------------------------------------------------------------------

def test_cq_ring_entries_pushed_and_consumed(mesh2):
    """flush_send pushes per-entry CQEs (status + wr_id); poll_cq consumes
    them back to CQE_EMPTY."""
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="RC", msg_bytes=16, depth=4)

    @partial(compat.shard_map, mesh=mesh2, in_specs=P("rank", None),
             out_specs=(P(), P(), P(), P(), P()))
    def roundtrip(buf):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(cfg)
        qp, _ = verbs.post_send(dp, cfg, qp, buf[0], rank, src=0)
        qp, _ = verbs.post_send(dp, cfg, qp, buf[0], rank, src=0)
        qp, _ = verbs.flush_send(dp, cfg, qp, rank, src=0, dst=1)
        status_after_flush = qp["cq_status"]
        wrid_after_flush = qp["cq_wrid"]
        occ = verbs.cq_occupancy(qp)
        _, qp, _ = verbs.poll_cq(dp, cfg, qp, rank, poller=1)
        return (status_after_flush, wrid_after_flush, occ,
                qp["cq_status"], verbs.cq_occupancy(qp))

    st, wrid, occ, st2, occ2 = jax.jit(roundtrip)(
        jnp.zeros((2, 16), jnp.uint8))
    np.testing.assert_array_equal(
        np.asarray(st)[:2], [verbs.CQE_SEND, verbs.CQE_SEND])
    np.testing.assert_array_equal(np.asarray(wrid)[:2], [0, 1])
    assert int(occ) == 2
    assert int(occ2) == 0                       # poll drained the ring
    assert np.all(np.asarray(st2) == verbs.CQE_EMPTY)


def test_cq_ring_sheds_on_overflow(mesh2):
    """Unpolled CQEs are never overwritten: pushes past the ring's free
    space are shed and occupancy stays within the ring size."""
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="RC", msg_bytes=16, depth=4, cq_depth=4)

    @partial(compat.shard_map, mesh=mesh2, in_specs=P("rank", None),
             out_specs=(P(), P(), P()))
    def overrun(buf):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(cfg)
        for _ in range(2):           # 2 × (4 posts + flush), never polled
            for _ in range(4):
                qp, _ = verbs.post_send(dp, cfg, qp, buf[0], rank, src=0)
            qp, _ = verbs.flush_send(dp, cfg, qp, rank, src=0, dst=1)
        return verbs.cq_occupancy(qp), qp["cq_hwm"], qp["cq_wrid"]

    occ, hwm, wrid = jax.jit(overrun)(jnp.zeros((2, 16), jnp.uint8))
    assert int(occ) == 4 and int(hwm) == 4      # ring never overfilled
    np.testing.assert_array_equal(np.asarray(wrid), [0, 1, 2, 3])


# ---------------------------------------------------------------------------
# regression: READ must not fabricate send completions
# ---------------------------------------------------------------------------

def test_read_completes_no_posted_sends(mesh2):
    dp = _dp("cord", mesh2)
    cfg = verbs.QPConfig(transport="RC", msg_bytes=16, depth=4)

    @partial(compat.shard_map, mesh=mesh2, in_specs=P("rank", None),
             out_specs=(P(), P(), P()))
    def readback(buf):
        rank = jax.lax.axis_index("rank")
        qp = verbs.qp_init(cfg)
        qp, _ = verbs.post_send(dp, cfg, qp, buf[0], rank, src=0)
        qp, _ = verbs.post_send(dp, cfg, qp, buf[0], rank, src=0)
        # a one-sided READ moves remote memory — the two posted sends
        # stay pending (no flush has run for them)
        qp, _ = verbs.flush_send(dp, cfg, qp, rank, src=0, dst=1, op="read")
        phantom, qp, _ = verbs.poll_cq(dp, cfg, qp, rank, poller=0)
        # flushing the send queue then completes them for real
        qp, _ = verbs.flush_send(dp, cfg, qp, rank, src=0, dst=1, op="send")
        real, qp, _ = verbs.poll_cq(dp, cfg, qp, rank, poller=1)
        return phantom, real, qp["cq_sent"]

    phantom, real, cq_sent = jax.jit(readback)(jnp.zeros((2, 16), jnp.uint8))
    assert int(phantom) == 0     # was 2 before the fix
    assert int(real) == 2
    assert int(cq_sent) == 2


# ---------------------------------------------------------------------------
# regression: msg_bytes must divide by the slot dtype size
# ---------------------------------------------------------------------------

def test_msg_bytes_must_match_dtype_itemsize():
    with pytest.raises(verbs.TransportError):
        verbs.QPConfig(msg_bytes=6, dtype="float32")   # 6 // 4 truncates
    with pytest.raises(verbs.TransportError):
        verbs.QPConfig(msg_bytes=2, dtype="float32")   # 2 // 4 == 0 slots
    cfg = verbs.QPConfig(msg_bytes=8, dtype="float32")
    assert verbs.qp_init(cfg)["send_ring"].shape == (cfg.depth, 2)
    with pytest.raises(verbs.TransportError):
        verbs.qp_init(verbs.QPConfig(msg_bytes=6), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# regression: a first sampled token == EOS must finish the request
# ---------------------------------------------------------------------------

class _EOSModel:
    """Stub model whose argmax token is always ``eos`` and which counts
    calls into its slot decode step (the engine's first decode tick would
    make one)."""

    def __init__(self, vocab=8, eos=1):
        self.vocab, self.eos = vocab, eos
        self.decode_calls = 0

    def init_cache(self, batch, cache_len):
        # batch on axis 1, as in every family's cache
        return {"len": jnp.zeros((1, batch), jnp.int32)}

    def _logits(self, b, s):
        return jnp.zeros((b, s, self.vocab)) \
            .at[:, :, self.eos].set(10.0)

    def prefill(self, params, batch, cache, dp=None, last_pos=None):
        toks = batch["tokens"]
        return self._logits(toks.shape[0], toks.shape[1]), cache

    def decode_step_slots(self, params, tok, cache, pos, dp=None):
        self.decode_calls += 1
        return self._logits(tok.shape[0], 1), cache


def test_engine_stops_on_first_token_eos():
    from repro.serve.engine import Engine, Request

    model = _EOSModel()
    eng = Engine(model, params={}, cfg=ModelConfig(),
                 serve=ServeConfig(max_batch=2, max_new_tokens=16),
                 dp=None, eos_id=model.eos)
    reqs = [Request(rid=0, prompt=np.array([3, 4], np.int32)),
            Request(rid=1, prompt=np.array([5], np.int32))]
    done = eng.run(reqs)
    for r in done:
        assert r.done
        assert r.out_tokens == [model.eos]   # was 16 tokens before the fix
    # ...and no decode step ever ran for an all-EOS batch
    assert model.decode_calls == 0
