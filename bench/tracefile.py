"""The profiler trace: taking it, and reducing an ``.xplane.pb`` to busy
time, kernel time and bytes, the top device ops and the idle gaps.

Reduction rules (the numbers every PR reads the same way):

* The window is the host span named ``bench/window`` (a
  ``TraceAnnotation`` the harness opens and closes); its length is
  ``window_s``.
* A device is a plane named ``/device:TPU:<n>``; its ops are the events
  of its ``XLA Ops`` line.  Busy time is the union of those op intervals
  inside the window; ``busy_s`` is its mean over the devices.
* Ops are kept by HLO instruction name, each with its own time (its
  interval minus the ops nested in it, as a ``while`` holds the ops of
  its body), so a kernel is found by name (a Pallas kernel's instruction
  is named after its jitted function).  A custom call's bytes are those
  of its array operands, read once and written back once (``2 *``
  operand bytes), taken from the shapes in the op's own HLO text.
* An idle gap is a stretch of the window in which a device runs no op.
  It is named after the shortest host event (``/host:CPU``) covering its
  middle: what the host was doing meanwhile.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

HOST_PLANE = "/host:CPU"
WINDOW = "bench/window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+)\s*=")
_SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
# gaps shorter than this are the ordinary seams between two ops
MIN_GAP_NS = 2_000.0


# ---------------------------------------------------------------------------
# taking the trace
# ---------------------------------------------------------------------------

class Tracer:
    """Profile a stretch of a run into ``log_dir``: ``start()`` opens the
    trace and the ``bench/window`` span, ``stop()`` closes both and
    returns the ``.xplane.pb`` path.  The Python tracer stays off; the
    host's JAX dispatch events stay on (they name the idle gaps)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()

    def stop(self) -> str:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return newest_xplane(self.log_dir)


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


# ---------------------------------------------------------------------------
# reducing it
# ---------------------------------------------------------------------------

def op_name(text: str) -> str:
    """HLO instruction name of an op event (``%fusion.3 = ...`` ->
    ``fusion.3``); other event names pass through."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def operand_bytes(text: str) -> int:
    """Bytes of the array operands of a custom call, from the shapes
    inside ``custom-call(...)`` in its HLO text."""
    if "custom-call(" not in text:
        return 0
    args = text.split("custom-call(", 1)[1].split(")", 1)[0]
    total = 0
    for dt, dims in _SHAPE.findall(args):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES.get(dt, 4)
    return total


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(evs) -> list[float]:
    """Each op's own time: its interval minus the ops nested inside it (a
    ``while`` op spans the ops of its body on the same line)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1]))
    own = [e - s for s, e, _ in evs]
    stack = []
    for i in order:
        s, e, _ = evs[i]
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce_xplane(path: str, top: int = 10) -> dict:
    """Reduce one trace file (see the module docstring).

    Returns ``window_s``, ``busy_s`` (mean over devices), ``devices`` (per
    device busy seconds), ``ops`` (per instruction name, summed over
    devices: ``[seconds, calls, bytes]``), ``device_ops`` and
    ``idle_gaps`` (the ``top`` largest, ``[name, seconds]``, seconds
    averaged over devices)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    host = next((p for p in planes if p.name == HOST_PLANE), None)
    host_events = []
    window = None
    if host is not None:
        for line in host.lines:
            for ev in line.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if ev.name == WINDOW:
                    window = (s, s + d)
                elif d > 0:
                    host_events.append((s, s + d, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    w0, w1 = window
    devices = [p for p in planes if _DEVICE.match(p.name)]
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane")

    busy_by_dev = {}
    ops = defaultdict(lambda: [0.0, 0, 0])
    gaps_total = defaultdict(float)
    host_events.sort()
    for plane in devices:
        line = next((ln for ln in plane.lines if ln.name == "XLA Ops"), None)
        evs = []
        for ev in (line.events if line is not None else ()):
            s, e = _clip(float(ev.start_ns),
                         float(ev.start_ns) + float(ev.duration_ns), w0, w1)
            if e > s:
                evs.append((s, e, ev.name))
        for (s, e, name), own in zip(evs, _self_times(evs)):
            acc = ops[op_name(name)]
            acc[0] += own * 1e-9
            acc[1] += 1
            if "custom-call(" in name:
                acc[2] += 2 * operand_bytes(name)
        merged = _union([(s, e) for s, e, _ in evs])
        busy_by_dev[plane.name] = sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 - g0 >= MIN_GAP_NS]
        labels = _host_labels(host_events, [(a + b) / 2 for a, b in gaps])
        for (g0, g1), label in zip(gaps, labels):
            gaps_total[label] += (g1 - g0) * 1e-9
    n = len(devices)
    rank = lambda d: sorted(([k, v / n] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy_by_dev.values()) / n,
            "devices": busy_by_dev,
            "ops": dict(ops),
            "device_ops": rank({k: v[0] for k, v in ops.items()}),
            "idle_gaps": rank(gaps_total)}


def _host_labels(host_events, times) -> list[str]:
    """For each of the increasing ``times``, the shortest host event that
    covers it (none: ``host idle``).  One sweep over the events sorted by
    start, with a heap of the started ones keyed by duration."""
    import heapq
    heap, out, i = [], [], 0
    for t in times:
        while i < len(host_events) and host_events[i][0] <= t:
            s, e, name = host_events[i]
            heapq.heappush(heap, (e - s, e, name))
            i += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "host idle")
    return out




def kernel_totals(trace: dict, prefixes) -> tuple[float, int, int]:
    """``(seconds, calls, bytes)`` of the ops whose names start with any of
    ``prefixes``, summed over devices."""
    sec, calls, nbytes = 0.0, 0, 0
    for name, (s, c, b) in trace["ops"].items():
        if any(name.startswith(p) for p in prefixes):
            sec, calls, nbytes = sec + s, calls + c, nbytes + b
    return sec, calls, nbytes


__all__ = ["Tracer", "newest_xplane", "reduce_xplane", "kernel_totals",
           "op_name", "operand_bytes", "WINDOW"]
