"""Family × stack serving conformance matrix.

Every config preset — including the downscaled big-model shims
(``arctic-480b``, ``llava-next-34b``) — is driven through the full
serving stack: continuous batching on persistent slots, timeline
snapshots, the elastic slot-budget trigger, and preemption with resume.
The matrix asserts the properties the converged-dataplane story depends
on:

* continuous ≡ the batch-1 greedy reference at temperature 0 (per
  family: each request's plain prefill-then-decode loop, served alone),
* preempt → resume is EXACT at temperature 0 (the emitted tokens are the
  snapshot; recompute-based resume must replay them bit-identically,
  including through the mamba/xLSTM recurrences),
* timeline artifacts save/load/validate with per-tick gauges,
* a ThresholdWatcher over the serve timeline trips and its slot-budget
  response is enforced,
* paged KV raises the family-naming ServeError on non-pageable caches.

The whole module is marked ``family`` (and ``slow``): tier-1 skips it via
pytest.ini addopts; CI runs it as its own `pytest -m family` lane.
"""

import os

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, get_model_config
from repro.configs.base import ServeConfig
from repro.core import CounterTimeline, ThresholdWatcher, validate_timeline
from repro.models import build_model
from repro.serve import Engine, Request, ServeError

pytestmark = [pytest.mark.family, pytest.mark.slow]

# families whose decode cache is a pure {"k","v"} rank-5 stripe — the only
# layout the block pool can page
_PAGEABLE = ("dense", "moe", "vlm")

_CACHE: dict = {}


def family_model(arch):
    """(cfg, model, params) for one arch's smoke shim, built once per
    session — every preset in ARCHS goes through the same path."""
    if arch not in _CACHE:
        cfg = get_model_config(arch, smoke=True)
        model = build_model(cfg)
        _CACHE[arch] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _CACHE[arch]


def _requests(lengths, max_new=5, tenants=None):
    return [Request(rid=i,
                    prompt=np.asarray((np.arange(n) + 3 * i) % 97, np.int32),
                    max_new_tokens=max_new,
                    tenant=tenants[i % len(tenants)] if tenants else "default")
            for i, n in enumerate(lengths)]


def _outs(done):
    return {r.rid: list(r.out_tokens) for r in done}


def _serve_cfg(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("kv_cache_len", 64)
    return ServeConfig(**kw)


@pytest.mark.parametrize("arch", ARCHS)
def test_continuous_matches_reference_temp0(arch, greedy_reference):
    """Greedy continuous batching ≡ the batch-1 reference loop (prefill,
    then scalar-position decode, one request alone), every family."""
    cfg, model, params = family_model(arch)
    cont = Engine(model, params, cfg, _serve_cfg(), eos_id=-1)
    reqs = _requests([8] * 5)
    out_c = _outs(cont.run(reqs))
    want = greedy_reference(model, params, [r.prompt for r in reqs], 5)
    assert out_c == dict(enumerate(want))
    assert all(len(v) == 5 for v in out_c.values())
    # ONE decode compile regardless of family: the fixed-shape slot step
    assert cont.decode_compile_count() == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_serve_mixed_lengths_and_tenants(arch):
    """Continuous serve dry-run: varied prompt lengths, two tenants, more
    requests than slots — everything completes with its full budget."""
    cfg, model, params = family_model(arch)
    eng = Engine(model, params, cfg, _serve_cfg(), eos_id=-1)
    done = eng.run(_requests([7, 9, 12, 8, 11], tenants=("a", "b")))
    assert len(done) == 5
    assert all(len(r.out_tokens) == 5 for r in done)
    rep = eng.tenant_report()
    assert rep["a"]["tokens"] + rep["b"]["tokens"] == 25


@pytest.mark.parametrize("arch", ARCHS)
def test_preempt_resume_exact(arch):
    """A mid-decode slot-budget preemption must resume exactly: the
    preempted run's outputs equal the undisturbed run's, per family —
    including the recurrent families, whose resume re-prefills the
    emitted prefix through the chunked scans rather than replaying
    sequential decode steps."""
    cfg, model, params = family_model(arch)
    lengths = [7, 9, 11]
    base = Engine(model, params, cfg, _serve_cfg(max_new_tokens=6), eos_id=-1)
    out_base = _outs(base.run(_requests(lengths, max_new=6)))

    eng = Engine(model, params, cfg, _serve_cfg(max_new_tokens=6), eos_id=-1)
    step, calls = eng._step_slots, {"n": 0}

    def spy(*a):
        calls["n"] += 1
        if calls["n"] == 3:          # two residents mid-decode by now
            eng.set_slot_budget(1)
        return step(*a)

    eng._step_slots = spy
    out_pre = _outs(eng.run(_requests(lengths, max_new=6)))
    rep = eng.tenant_report()["default"]
    assert rep["preemptions"] >= 1 and rep["restores"] >= 1
    assert out_pre == out_base


@pytest.mark.parametrize("arch", ARCHS)
def test_timeline_artifact(arch, tmp_path):
    """Per-tick serve snapshots produce a valid, loadable timeline
    artifact with slot gauges and nonzero served tokens, every family."""
    cfg, model, params = family_model(arch)
    tl = CounterTimeline(source=f"family/{arch}")
    eng = Engine(model, params, cfg, _serve_cfg(), eos_id=-1, obs=tl)
    eng.run(_requests([8, 9, 10]))
    assert len(tl.samples) >= 3
    assert any(s["gauges"]["active_slots"] > 0 for s in tl.samples)
    path = tl.save(os.path.join(tmp_path, f"{arch}_timeline.json"))
    doc = CounterTimeline.load(path)          # load() re-validates
    validate_timeline(doc)
    last = doc["samples"][-1]
    # served tokens ride the counter block's bytes column
    # (Engine.runtime_counters)
    assert last["tenants"].get("default", {}).get("bytes", 0) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_elastic_trigger_drives_slot_budget(arch):
    """The serve-side elastic loop, per family: a ThresholdWatcher over
    the engine's own timeline trips on sustained decode traffic, and the
    trigger's response (``set_slot_budget(1)``) is enforced on the next
    run — the active-slot gauge never exceeds the shrunken budget."""
    cfg, model, params = family_model(arch)
    tl = CounterTimeline(source=f"family/{arch}")
    eng = Engine(model, params, cfg, _serve_cfg(), eos_id=-1, obs=tl)
    eng.run(_requests([8, 9, 10, 8]))
    # chunks_s carries slot-occupancy steps/s (Engine.runtime_counters):
    # it moves on EVERY tick with an active slot — unlike tokens (bytes),
    # which land in a lump at completion — so a tiny threshold sees the
    # consecutive nonzero windows the sustain logic needs
    watcher = ThresholdWatcher({"chunks_s": 1e-9}, sustain=2, cooldown=64)
    fired = watcher.observe(tl)
    assert len(watcher.triggers) >= 1
    tl.record_event("slot_budget", step=int(fired[0]["step"]),
                    tenant=fired[0]["tenant"], detail={"budget": 1})
    assert tl.events and tl.events[-1]["kind"] == "slot_budget"

    tl2 = CounterTimeline(source=f"family/{arch}/shrunk")
    eng2 = Engine(model, params, cfg, _serve_cfg(), eos_id=-1, obs=tl2)
    eng2.set_slot_budget(1)
    done = eng2.run(_requests([8, 9, 10]))
    assert len(done) == 3 and all(len(r.out_tokens) == 5 for r in done)
    assert max(s["gauges"]["active_slots"] for s in tl2.samples) <= 1


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_support_matches_cache_layout(arch):
    """block_size > 0 either pages (pure rank-5 {k,v} stripe) or raises
    the family-naming ServeError — never a silent fallback."""
    cfg, model, params = family_model(arch)
    sc = _serve_cfg(kv_cache_len=64, block_size=8, n_blocks=24)
    if cfg.family in _PAGEABLE:
        eng = Engine(model, params, cfg, sc, eos_id=-1)
        assert eng.paged
        done = eng.run(_requests([8] * 3))
        assert all(len(r.out_tokens) == 5 for r in done)
    else:
        with pytest.raises(ServeError) as ei:
            Engine(model, params, cfg, sc, eos_id=-1)
        msg = str(ei.value)
        assert cfg.family in msg          # names the unsupported family
        assert "block_size=0" in msg      # names the flag to flip
