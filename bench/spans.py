#!/usr/bin/env python3
"""The serving engine's spans and named programs in a profiler trace, and
the four per-layer readings taken from them.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

runs one cell traced, as ``bench/run.py --trace 1`` does, and prints its
result line, then one more JSON line: ``readings`` (below) and the keys
of ``reduce_spans`` they come from.  ``drivers/serve.py`` reduces its trace
with ``tracefile.reduce_xplane`` alone and deletes the file; for this
one run that function is wrapped, so the same file is also reduced here.

``reduce_spans(path)`` adds to the rules of ``tracefile.py``:

* A program is a module on a device's ``XLA Modules`` line, by its name
  without the ``(<hash>)`` suffix (``jit_serve_decode``); its time is
  the module's interval inside the window, and its calls are the modules
  that start inside the window, so seconds over calls is a module's
  length even where the window cuts one at each end.
* A span is a host event named ``serve/<phase>`` (the serving engine's
  ``TraceAnnotation``s; their arguments are event stats, not part of
  the name); its self time is its interval inside the window minus the
  ``serve/`` spans nested in it on the same line.
* Every idle gap's seconds are kept under its label
  (``tracefile.reduce_xplane`` keeps only the top ten), and are also
  split over the innermost ``serve/`` spans it overlaps, each taking the
  part of the gap it covers (the rest goes to ``(no serve span)``).

These readings are not yet metrics of ``BENCHMARK.json``: a reader of
``bench/metrics/`` sees only what ``tracefile.reduce_xplane`` returns.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

import tracefile
from tracefile import HOST_PLANE, MIN_GAP_NS, WINDOW, _clip, _union

SPAN_PREFIX = "serve/"
NO_SPAN = "(no serve span)"
DECODE = "jit_serve_decode"
PREFILL = ("jit_serve_prefill", "jit_serve_chunk_scatter",
           "jit_serve_pool_insert")
# the engine's own bookkeeping in a tick, apart from the model's programs
BOOKKEEPING = ("serve/schedule", "serve/blocks", "serve/emit",
               "serve/observe")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")


def module_name(name: str) -> str:
    """A module event's name without its hash (``jit_f(123)`` ->
    ``jit_f``)."""
    m = _MODULE.match(name)
    return m.group(1) if m else name


def _clipped(events, w0, w1):
    """``(start, end, name)`` of the events inside the window, clipped."""
    out = []
    for ev in events:
        s, e = _clip(float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns), w0, w1)
        if e > s:
            out.append((s, e, ev.name))
    return out


def _line(plane, name):
    return next((ln for ln in plane.lines if ln.name == name), None)


def reduce_spans(path: str) -> dict:
    """Reduce one trace file (see the module docstring).

    Returns ``programs`` (per module name, summed over devices:
    ``[seconds, calls]``), ``spans`` (per ``serve/`` span name:
    ``[seconds, count, self_seconds]``), ``idle_by_label`` (every idle
    gap label with its seconds, averaged over devices) and
    ``idle_by_span`` (the idle seconds each innermost ``serve/`` span
    covers, averaged over devices)."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    host = next((p for p in planes if p.name == HOST_PLANE), None)
    host_events, span_lines, window = [], [], None
    for line in (host.lines if host is not None else ()):
        spans = []
        for ev in line.events:
            s, d = float(ev.start_ns), float(ev.duration_ns)
            if ev.name == WINDOW:
                window = (s, s + d)
            elif d > 0:
                host_events.append((s, s + d, ev.name))
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(ev)
        span_lines.append(spans)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    w0, w1 = window
    devices = [p for p in planes if tracefile._DEVICE.match(p.name)]
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane")

    programs = defaultdict(lambda: [0.0, 0])
    by_label = defaultdict(float)
    by_span = defaultdict(float)
    host_events.sort()
    pieces = _innermost(span_lines, w0, w1)
    for plane in devices:
        ops = _line(plane, "XLA Ops")
        merged = _union([(s, e) for s, e, _ in
                         _clipped(ops.events if ops else (), w0, w1)])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 - g0 >= MIN_GAP_NS]
        labels = tracefile._host_labels(host_events,
                                        [(a + b) / 2 for a, b in gaps])
        for (g0, g1), label in zip(gaps, labels):
            by_label[label] += (g1 - g0) * 1e-9
        for name, ns in _split_gaps(gaps, pieces).items():
            by_span[name] += ns * 1e-9
        mods = _line(plane, "XLA Modules")
        _add_programs(programs, mods.events if mods else (), w0, w1)
    n = len(devices)
    return {"programs": dict(programs),
            "spans": _span_totals(span_lines, w0, w1),
            "idle_by_label": {k: v / n for k, v in by_label.items()},
            "idle_by_span": {k: v / n for k, v in by_span.items()}}


def _add_programs(programs, events, w0, w1) -> None:
    """Add each module event's seconds inside the window to its program's
    ``[seconds, calls]``; a call counts where the module starts inside
    the window."""
    for ev in events:
        s0 = float(ev.start_ns)
        s, e = _clip(s0, s0 + float(ev.duration_ns), w0, w1)
        if e > s:
            acc = programs[module_name(ev.name)]
            acc[0] += (e - s) * 1e-9
            acc[1] += int(s0 >= w0)


def _span_totals(span_lines, w0, w1) -> dict:
    """``[seconds, count, self_seconds]`` per span name, over the lines
    (one list of ``serve/`` span events per host line)."""
    out = defaultdict(lambda: [0.0, 0, 0.0])
    for events in span_lines:
        evs = _clipped(events, w0, w1)
        for (s, e, name), own in zip(evs, tracefile._self_times(evs)):
            acc = out[name]
            acc[0] += (e - s) * 1e-9
            acc[1] += 1
            acc[2] += own * 1e-9
    return dict(out)


def _innermost(span_lines, w0, w1) -> list:
    """The window's time under ``serve/`` spans as sorted, disjoint
    ``(start, end, name)`` pieces, each named after the innermost span
    covering it (spans nest on their line; where two lines overlap, the
    earlier piece keeps the time)."""
    pieces = []
    for events in span_lines:
        stack, t = [], None
        for s, e, name in sorted(_clipped(events, w0, w1),
                                 key=lambda x: (x[0], -x[1])):
            while stack and stack[-1][0] <= s:
                end, inner = stack.pop()
                pieces.append((t, end, inner))
                t = end
            if stack:
                pieces.append((t, s, stack[-1][1]))
            stack.append((e, name))
            t = s
        while stack:
            end, inner = stack.pop()
            pieces.append((t, end, inner))
            t = end
    out, last = [], float("-inf")
    for s, e, name in sorted(p for p in pieces if p[1] > p[0]):
        s = max(s, last)
        if e > s:
            out.append((s, e, name))
            last = e
    return out


def _split_gaps(gaps, pieces) -> dict:
    """Nanoseconds of the sorted ``gaps`` that each piece's name covers
    (``_innermost``); what no piece covers goes to ``NO_SPAN``."""
    out = defaultdict(float)
    j = 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        rest, k = g1 - g0, j
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, name = pieces[k]
            d = min(e, g1) - max(s, g0)
            out[name] += d
            rest -= d
            k += 1
        if rest > 0:
            out[NO_SPAN] += rest
    return dict(out)


# ---------------------------------------------------------------------------
# the readings: each takes the reduction and returns None where the
# program names no serve program or emits no span
# ---------------------------------------------------------------------------

def step_ms_decode(t: dict):
    """Device time of one decode step: the ``jit_serve_decode`` program's
    seconds over its calls, each chip's calls counted on their own."""
    sec, calls = t.get("programs", {}).get(DECODE, (0.0, 0))
    return 1e3 * sec / calls if calls else None


def prefill_pct_device(t: dict):
    """Device time of prompt processing (the whole and chunked prefill
    programs and those that write their cache into the pool) over that
    of every program, both summed over the chips."""
    progs = t.get("programs", {})
    total = sum(sec for sec, _ in progs.values())
    if DECODE not in progs or total <= 0:
        return None
    pre = sum(sec for name, (sec, _) in progs.items()
              if name.startswith(PREFILL))
    return 100.0 * pre / total


def host_ms_tick(t: dict):
    """Self time of the engine's bookkeeping spans per decode tick (the
    count of ``serve/decode`` spans)."""
    spans = t.get("spans", {})
    ticks = spans.get("serve/decode", (0.0, 0, 0.0))[1]
    if not ticks:
        return None
    return 1e3 * sum(spans[p][2] for p in BOOKKEEPING if p in spans) / ticks


def engine_idle_pct_decode(t: dict):
    """Share of the device's idle time that falls inside the engine's
    bookkeeping spans (``idle_by_span``)."""
    if "serve/decode" not in t.get("spans", {}):
        return None
    idle = t.get("idle_by_span", {})
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * sum(idle.get(p, 0.0) for p in BOOKKEEPING) / total


READINGS = {"step_ms.decode": step_ms_decode,
            "prefill_pct.device": prefill_pct_device,
            "host_ms.tick": host_ms_tick,
            "engine_idle_pct.decode": engine_idle_pct_decode}


def readings(t: dict) -> dict:
    """Every reading that finds something to read in the reduction."""
    out = {name: f(t) for name, f in READINGS.items()}
    return {k: v for k, v in out.items() if v is not None}


def main(argv=None) -> int:
    import run
    kept = {}
    reduce = tracefile.reduce_xplane

    def both(path, *args, **kwargs):
        kept.update(reduce_spans(path))
        return reduce(path, *args, **kwargs)

    tracefile.reduce_xplane = both
    try:
        rc = run.main(list(sys.argv[1:] if argv is None else argv)
                      + ["--trace", "1"])
    finally:
        tracefile.reduce_xplane = reduce
    if rc:
        return rc
    if not kept:
        print("spans: the run reduced no trace", file=sys.stderr)
        return 3
    print(json.dumps({"readings": readings(kept), **kept}))
    return 0


__all__ = ["reduce_spans", "readings", "READINGS", "module_name",
           "SPAN_PREFIX", "NO_SPAN"]


if __name__ == "__main__":
    sys.exit(main())
