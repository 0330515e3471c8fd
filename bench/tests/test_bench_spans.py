"""The span reduction's program, span and idle keys (bench/spans.py), and
the readings taken from them, on a small trace recorded on one TPU v5 lite by
``record_serve_tiny.py``: a one-layer model served through the paged,
chunked engine, traced from ``on_tick`` as ``drivers/serve.py`` traces a
cell, over two ticks that hold a whole prefill, the last chunk of a
chunked one and two decode steps."""

import sys
from pathlib import Path
from collections import defaultdict
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import spans  # noqa: E402
import tracefile  # noqa: E402

DATA = BENCH / "tests" / "data"
SERVE_TINY = str(DATA / "serve_tiny.xplane.pb")
TINY = str(DATA / "tiny.xplane.pb")
METRICS = list(spans.READINGS)
PHASES = spans.BOOKKEEPING


@pytest.fixture(scope="module")
def serve_tiny():
    # what the accepted reduction gives, with the span reduction's keys
    return {**tracefile.reduce_xplane(SERVE_TINY),
            **spans.reduce_spans(SERVE_TINY)}


def _read(name, trace):
    return spans.READINGS[name](trace)


def test_trace_is_small():
    assert Path(SERVE_TINY).stat().st_size < 300 * 1024


def test_programs_by_name_without_hash(serve_tiny):
    progs = serve_tiny["programs"]
    assert not any(name.endswith(")") for name in progs)
    calls = {name: c for name, (_, c) in progs.items()
             if name.startswith("jit_serve_")}
    assert calls == {"jit_serve_decode": 2, "jit_serve_prefill": 1,
                     "jit_serve_pool_insert": 1,
                     "jit_serve_prefill_chunk": 1,
                     "jit_serve_chunk_scatter": 1}
    assert progs["jit_serve_decode"] == pytest.approx([3.418e-05, 2])
    # a module holds its ops: the modules' time covers the busy union
    total = sum(s for s, _ in progs.values())
    assert serve_tiny["busy_s"] <= total <= serve_tiny["window_s"]


def test_spans_count_and_self_time(serve_tiny):
    got = serve_tiny["spans"]
    # the trace opens in tick 2's on_tick and closes in tick 4's: tick 3
    # is whole, tick 4 lacks its observe span and its tick span
    count = {name: c for name, (_, c, _) in got.items()}
    assert count == {"serve/tick": 1, "serve/schedule": 2,
                     "serve/prefill": 2, "serve/blocks": 2,
                     "serve/decode": 2, "serve/sample": 2, "serve/emit": 2,
                     "serve/observe": 1}
    for name, (sec, _, own) in got.items():
        assert 0 < own <= sec <= serve_tiny["window_s"]
    assert got["serve/decode"][0] == pytest.approx(3.338779e-3)
    assert serve_tiny["window_s"] == pytest.approx(3.1430379e-2)
    # the tick holds every phase; tick 3's grant runs its whole prefill
    # inside serve/schedule; nothing else nests
    for name in ("serve/tick", "serve/schedule"):
        assert got[name][2] < got[name][0]
    for name in ("serve/blocks", "serve/emit", "serve/observe",
                 "serve/prefill", "serve/decode", "serve/sample"):
        assert got[name][2] == pytest.approx(got[name][0])


def test_idle_by_label_covers_every_gap(serve_tiny):
    idle = serve_tiny["idle_by_label"]
    assert sum(idle.values()) == pytest.approx(
        serve_tiny["window_s"] - serve_tiny["busy_s"], rel=1e-3)
    # the split over spans shares the same gaps out whole
    assert sum(serve_tiny["idle_by_span"].values()) == pytest.approx(
        sum(idle.values()))
    # idle_gaps is its top ten, unchanged
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    assert [list(kv) for kv in top] == serve_tiny["idle_gaps"]
    assert "host idle" not in dict(serve_tiny["idle_gaps"][:1])


def test_readings(serve_tiny):
    t = serve_tiny
    sec, calls = t["programs"]["jit_serve_decode"]
    assert _read("step_ms.decode", t) == pytest.approx(1e3 * sec / calls)
    assert _read("step_ms.decode", t) == pytest.approx(1.709e-2)
    pre = sum(t["programs"][k][0] for k in
              ("jit_serve_prefill", "jit_serve_prefill_chunk",
               "jit_serve_chunk_scatter", "jit_serve_pool_insert"))
    total = sum(sec for sec, _ in t["programs"].values())
    assert _read("prefill_pct.device", t) == pytest.approx(
        100 * pre / total)
    own = sum(t["spans"][p][2] for p in PHASES)
    assert _read("host_ms.tick", t) == pytest.approx(1e3 * own / 2)
    for name in METRICS:
        assert _read(name, t) > 0
    assert spans.readings(t) == {name: _read(name, t) for name in METRICS}
    # the device idles through the whole tick here, so each bookkeeping
    # span's whole length is idle time it owns
    idle = t["idle_by_span"]
    for p in PHASES:
        assert idle[p] == pytest.approx(t["spans"][p][2], rel=1e-3)
    assert _read("engine_idle_pct.decode", t) == pytest.approx(
        100 * own / sum(idle.values()), rel=1e-3)
    assert _read("engine_idle_pct.decode", t) == pytest.approx(0.86608,
                                                               rel=1e-4)


@pytest.mark.parametrize("name", METRICS)
def test_readers_find_nothing_in_a_trace_without_the_engine(name):
    """A program that names no serve program and emits no span (the
    dataplane trace) reads nothing, and nothing raises."""
    assert _read(name, spans.reduce_spans(TINY)) is None
    assert _read(name, {}) is None


def test_prefill_share_is_zero_with_decode_and_no_prefill(serve_tiny):
    t = dict(serve_tiny)
    t["programs"] = {"jit_serve_decode": t["programs"]["jit_serve_decode"]}
    assert _read("prefill_pct.device", t) == 0.0


@pytest.mark.parametrize("text, want", [
    ("jit_serve_decode(9876543210)", "jit_serve_decode"),
    ("jit__lambda(15810656841452688566)", "jit__lambda"),
    ("jit_f", "jit_f"),
])
def test_module_name(text, want):
    assert spans.module_name(text) == want


def test_span_totals_clip_and_nest():
    ev = lambda s, e, name: SimpleNamespace(start_ns=s, duration_ns=e - s,
                                            name=name)
    line = [ev(0, 150, "serve/tick"), ev(10, 30, "serve/schedule"),
            ev(15, 25, "serve/prefill"), ev(40, 60, "serve/decode"),
            ev(90, 140, "serve/emit")]
    got = spans._span_totals([line, [ev(5, 15, "serve/tick")]], 0, 120)
    ns = 1e-9
    assert got["serve/tick"] == pytest.approx([130 * ns, 2, 60 * ns])
    assert got["serve/schedule"] == pytest.approx([20 * ns, 1, 10 * ns])
    assert got["serve/prefill"] == pytest.approx([10 * ns, 1, 10 * ns])
    assert got["serve/decode"] == pytest.approx([20 * ns, 1, 20 * ns])
    # clipped at the window's end, with the tick that holds it
    assert got["serve/emit"] == pytest.approx([30 * ns, 1, 30 * ns])


def _ev(s, e, name):
    return SimpleNamespace(start_ns=s, duration_ns=e - s, name=name)


def test_idle_split_over_the_innermost_spans():
    """A gap is shared out over the spans it overlaps, each innermost
    span taking the part it covers, the rest under ``NO_SPAN``."""
    line = [_ev(0, 100, "serve/tick"), _ev(10, 30, "serve/sample"),
            _ev(30, 40, "serve/emit"), _ev(40, 46, "serve/observe"),
            _ev(60, 90, "serve/decode")]
    pieces = spans._innermost([line], 0, 120)
    assert pieces == [(0, 10, "serve/tick"), (10, 30, "serve/sample"),
                      (30, 40, "serve/emit"), (40, 46, "serve/observe"),
                      (46, 60, "serve/tick"), (60, 90, "serve/decode"),
                      (90, 100, "serve/tick")]
    got = spans._split_gaps([(20, 70), (95, 110)], pieces)
    assert got == {"serve/sample": 10, "serve/emit": 10,
                   "serve/observe": 6, "serve/tick": 14 + 5,
                   "serve/decode": 10, spans.NO_SPAN: 10}
    # the midpoint of the first gap (45) lies in serve/observe: the
    # split gives it the 6 ns it covers, not the gap's 50
    trace = {"spans": {"serve/decode": [0, 1, 0]},
             "idle_by_span": {k: v * 1e-9 for k, v in got.items()}}
    assert _read("engine_idle_pct.decode", trace) == pytest.approx(
        100 * 16 / 65)


def test_idle_split_without_spans():
    assert spans._innermost([[]], 0, 100) == []
    assert spans._split_gaps([(10, 30)], []) == {spans.NO_SPAN: 20}


def test_programs_count_calls_that_start_in_the_window():
    """A module the window cuts at its start adds its seconds and no
    call; one cut at the end adds both, so seconds over calls is a
    module's length when the step repeats."""
    programs = defaultdict(lambda: [0.0, 0])
    mods = [_ev(-60, 40, "jit_serve_decode(1)"),
            _ev(40, 140, "jit_serve_decode(1)"),
            _ev(140, 240, "jit_serve_decode(1)"),
            _ev(240, 340, "jit_serve_decode(1)"),
            _ev(340, 440, "jit_serve_decode(1)")]
    spans._add_programs(programs, mods, 0, 400)
    assert programs["jit_serve_decode"] == pytest.approx([400e-9, 4])
    trace = {"programs": dict(programs)}
    assert _read("step_ms.decode", trace) == pytest.approx(1e-4)
