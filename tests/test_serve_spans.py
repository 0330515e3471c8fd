"""The serving engine's ticks seen from outside: the tick-phase profiler
spans and named programs a ``jax.profiler`` trace of ``Engine.run``
carries (docs/observability.md "Spans"), and the ``on_tick`` hook, which
fires once per decode tick with or without a ``CounterTimeline``."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_model_config
from repro.configs.base import ServeConfig
from repro.core.obs import CounterTimeline
from repro.models import build_model
from repro.serve import Engine, Request

PAGED_CHUNKED = dict(max_batch=2, max_new_tokens=4, kv_cache_len=128,
                     prefill_chunk=16, block_size=8)
# 40 and 23 prefill in chunks of 16 (3 and 2 chunks), 8 whole
LENGTHS = [40, 8, 23]
CHUNKS = {0: [0, 16, 32], 1: [0], 2: [0, 16]}


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_model_config("gemma3-1b", smoke=True)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(lengths=LENGTHS, max_new=4):
    return [Request(rid=i, prompt=np.asarray((np.arange(n) + 3 * i) % 100,
                                             np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]


def _tokens(done):
    return {r.rid: r.out_tokens for r in done}


def _count_steps(eng):
    """Count the decode steps ``eng`` dispatches (one per decode tick)."""
    orig, n = eng._step_pool, {"steps": 0}

    def spy(*a):
        n["steps"] += 1
        return orig(*a)

    eng._step_pool = spy
    return n


# ---------------------------------------------------------------------------
# spans and named programs in a profiler trace
# ---------------------------------------------------------------------------

def _host_events(path):
    """``(start, end, name, stats)`` of every host event, per host line."""
    data = ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    return [[(float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns),
              ev.name, dict(ev.stats)) for ev in line.events]
            for line in host.lines]


@pytest.fixture(scope="module")
def traced(smoke_model, tmp_path_factory):
    """One untraced run and one run under a profiler session, on the same
    engine; the session's host events and the tick count."""
    cfg, model, params = smoke_model
    eng = Engine(model, params, cfg, ServeConfig(**PAGED_CHUNKED), eos_id=-1)
    plain = _tokens(eng.run(_requests()))
    ticks = {"n": 0}

    def on_tick(_engine):
        ticks["n"] += 1

    eng.on_tick = on_tick
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    with jax.profiler.trace(log_dir):
        under = _tokens(eng.run(_requests()))
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return {"plain": plain, "under": under, "ticks": ticks["n"],
            "lines": _host_events(path)}


def _spans(lines):
    return [ev for line in lines for ev in line
            if ev[2].startswith("serve/")]


def test_every_phase_span_nests_in_a_tick(traced):
    for line in traced["lines"]:
        ticks = [ev for ev in line if ev[2] == "serve/tick"]
        for s, e, name, stats in line:
            if not name.startswith("serve/") or name == "serve/tick":
                continue
            owner = [t for t in ticks if t[0] <= s and e <= t[1]]
            assert len(owner) == 1, f"{name} {stats} outside a serve/tick"
            if name == "serve/decode":
                assert stats["tick"] == owner[0][3]["tick"]
    names = {ev[2] for ev in _spans(traced["lines"])}
    assert names == {"serve/tick", "serve/schedule", "serve/prefill",
                          "serve/blocks", "serve/decode", "serve/sample",
                          "serve/emit", "serve/observe"}


def test_one_decode_span_per_decode_tick(traced):
    spans = _spans(traced["lines"])
    decode = [ev for ev in spans if ev[2] == "serve/decode"]
    assert traced["ticks"] > 0
    assert len(decode) == traced["ticks"]
    for phase in ("serve/sample", "serve/emit", "serve/observe"):
        assert sum(ev[2] == phase for ev in spans) == traced["ticks"]
    # every engine tick is one span, decode ticks or not
    ticks = sorted(ev[3]["tick"] for ev in spans if ev[2] == "serve/tick")
    assert ticks == list(range(1, len(ticks) + 1))
    # each request's first token comes from its prefill, the rest from
    # decode ticks
    emitted = sum(ev[3]["tokens"] for ev in spans if ev[2] == "serve/emit")
    assert emitted + len(LENGTHS) == \
        sum(len(t) for t in traced["under"].values())


def test_decode_span_counts_blocks(traced):
    """Paged decode spans carry the blocks the active slots hold and the
    blocks of the whole tables, ``max_batch × tables_len``: their ratio is
    the share of the tables the decode step can read."""
    b, bs = PAGED_CHUNKED["max_batch"], PAGED_CHUNKED["block_size"]
    tables_len = b * PAGED_CHUNKED["kv_cache_len"] // bs
    decode = [ev[3] for ev in _spans(traced["lines"])
              if ev[2] == "serve/decode"]
    assert decode
    for st in decode:
        assert st["table_blocks"] == b * tables_len
        assert st["active"] <= st["blocks"] < st["table_blocks"]


def test_prefill_spans_carry_rid_one_per_chunk(traced):
    prefill = [ev[3] for ev in _spans(traced["lines"])
               if ev[2] == "serve/prefill"]
    by_rid = {}
    for st in prefill:
        by_rid.setdefault(st["rid"], []).append(st["offset"])
    assert by_rid == CHUNKS
    for st in prefill:                   # real tokens, padding left out
        assert st["tokens"] == min(16, LENGTHS[st["rid"]] - st["offset"])


def test_host_plane_names_the_programs(traced):
    names = {ev[2] for line in traced["lines"] for ev in line}
    for prog in ("serve_decode", "serve_prefill_chunk", "serve_prefill",
                 "serve_chunk_scatter", "serve_pool_insert"):
        assert f"PjitFunction({prog})" in names
    assert not any(n.startswith("PjitFunction(<lambda>)") for n in names)


def test_tokens_equal_with_and_without_the_session(traced):
    assert traced["under"] == traced["plain"]


# ---------------------------------------------------------------------------
# on_tick: once per decode tick, with or without a timeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("obs_every", [None, 1, 3])
def test_on_tick_fires_once_per_tick(smoke_model, obs_every):
    """No timeline, ``obs_every=1`` (the snapshot and the hook must not
    fire the hook twice) and ``obs_every=3``: the hook fires on every
    decode tick, after the snapshot that is due."""
    cfg, model, params = smoke_model
    timeline = None if obs_every is None else CounterTimeline(source="t")
    eng = Engine(model, params, cfg, ServeConfig(**PAGED_CHUNKED), eos_id=-1,
                 obs=timeline, obs_every=obs_every or 1)
    steps = _count_steps(eng)
    seen = []

    def on_tick(engine):
        assert engine is eng
        seen.append((steps["steps"],
                     len(timeline.samples) if timeline else None))

    eng.on_tick = on_tick
    eng.run(_requests())
    assert steps["steps"] > 3
    assert [s for s, _ in seen] == list(range(1, steps["steps"] + 1))
    if timeline is not None:
        assert [n for _, n in seen] == [s // obs_every for s, _ in seen]


# ---------------------------------------------------------------------------
# the expert share's rows on the decode span
# ---------------------------------------------------------------------------

def test_decode_span_carries_expert_rows(tmp_path):
    """With MoE layers at an expert share, the rows its held experts
    computed in each paged decode step (``moe_rows``) and the (layer,
    held expert) pairs that had any (``moe_experts_hit``), summed over
    layers, arrive with the sampled tokens: the tick's ``serve/sample``
    span carries them, one per ``serve/decode`` span, and they add up to
    the engine's running totals."""
    cfg = get_model_config("trinity-mini", smoke=True)
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), cfg,
                 ServeConfig(**PAGED_CHUNKED), eos_id=-1)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(_requests())
    path = max(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    lines = _host_events(path)
    decode = sorted((ev for ev in _spans(lines) if ev[2] == "serve/decode"),
                    key=lambda ev: ev[0])
    sample = sorted((ev for ev in _spans(lines) if ev[2] == "serve/sample"),
                    key=lambda ev: ev[0])
    assert len(decode) == len(sample) == eng.moe_stats["ticks"] > 0
    moe_layers = cfg.num_layers - cfg.moe.first_dense
    for (d0, d1, _, dst), (s0, _, _, st) in zip(decode, sample):
        assert d1 <= s0                   # the sample follows its step
        assert "moe_rows" not in dst
        assert 0 < st["moe_experts_hit"] <= moe_layers * cfg.moe.held
        assert st["moe_experts_hit"] <= st["moe_rows"] <= \
            moe_layers * dst["active"] * cfg.moe.top_k
    for name in ("moe_rows", "moe_experts_hit"):
        assert sum(st[name] for *_, st in sample) == eng.moe_stats[name]
