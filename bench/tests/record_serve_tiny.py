#!/usr/bin/env python3
"""Record ``bench/tests/data/serve_tiny.xplane.pb``: a few ticks of a tiny
model served on one TPU through the paged, chunked continuous engine,
traced the way ``drivers/serve.py`` traces a cell (the trace opened and
closed from ``on_tick``, with the ``bench/window`` span).

    python3 bench/tests/record_serve_tiny.py [out_dir]

Writes ``serve_tiny.xplane.pb`` into ``out_dir`` (default
``bench/tests/data``, where the tests read it) and prints the span
reduction's keys (``bench/spans.py``) as one JSON line.
The file keeps every plane, line, event and event name; it drops what
``ProfileData`` does not expose and the reduction never reads, which is
most of its size: the ``/host:metadata`` plane (each program's HLO) and
the stats of each event's metadata (source locations, shapes).
"""

import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import spans  # noqa: E402
import tracefile  # noqa: E402

# the trace opens in the on_tick of this tick and closes in that of the last
FIRST, LAST = 2, 4


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return out, i


def _fields(buf: bytes):
    """``(number, raw bytes of the whole field)`` of a protobuf message,
    with a length-delimited field's payload as well."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        kind, payload = key & 7, None
        if kind == 0:
            _, i = _varint(buf, i)
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        elif kind == 2:
            n, i = _varint(buf, i)
            payload, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, buf[start:i], payload


def _delimited(number: int, payload: bytes) -> bytes:
    out, n = bytearray([number << 3 | 2]), len(payload)
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out) + payload


def slim(raw: bytes) -> bytes:
    """An ``XSpace`` without the ``/host:metadata`` plane and without the
    stats and metadata bytes of every ``XEventMetadata`` (fields 3 and 5;
    its id, name and display name stay)."""
    def meta(entry):                     # map entry: key 1, value 2
        out = b""
        for num, raw_f, payload in _fields(entry):
            if num == 2:
                payload = b"".join(r for n, r, _ in _fields(payload)
                                   if n not in (3, 5))
                raw_f = _delimited(2, payload)
            out += raw_f
        return out

    space = b""
    for num, raw_f, plane in _fields(raw):
        if num == 1:
            if any(n == 2 and p == b"/host:metadata"
                   for n, _, p in _fields(plane)):
                continue
            raw_f = _delimited(1, b"".join(
                _delimited(4, meta(p)) if n == 4 else r
                for n, r, p in _fields(plane)))
        space += raw_f
    return space


def main(out_dir: str = str(BENCH / "tests" / "data")) -> int:
    import jax

    from repro.configs.base import AttentionConfig, ModelConfig, ServeConfig
    from repro.models import build_model
    from repro.serve import Engine, Request

    if jax.default_backend() != "tpu":
        print("record_serve_tiny: needs a TPU", file=sys.stderr)
        return 2
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=128,
                      d_ff=256, vocab_size=256, max_seq_len=128,
                      attention=AttentionConfig(num_heads=2, num_kv_heads=1))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, cfg,
                 ServeConfig(max_batch=2, max_new_tokens=3, kv_cache_len=64,
                             prefill_chunk=16, block_size=8), eos_id=-1)

    def requests():
        # tick 1 grants 40 (3 chunks of 16) and 8 (whole), and 8 finishes
        # in tick 2; tick 3 grants 12 (whole) and lands the last chunk, so
        # ticks 3-4 hold a whole prefill, a chunk and two decode steps
        return [Request(rid=i, prompt=np.asarray((np.arange(n) + 3 * i)
                                                 % 100, np.int32),
                        max_new_tokens=3)
                for i, n in enumerate([40, 8, 12])]

    eng.run(requests())
    eng.run(requests())                  # every program compiled twice
    log_dir = os.path.join(out_dir, "serve_tiny_trace")
    tracer = tracefile.Tracer(log_dir)
    st = {"tick": 0, "xplane": None}

    def on_tick(_engine):
        st["tick"] += 1
        if st["tick"] == FIRST:
            tracer.start()
        elif st["tick"] == LAST:
            st["xplane"] = tracer.stop()

    eng.on_tick = on_tick
    eng.run(requests())
    if st["xplane"] is None:
        print(f"record_serve_tiny: the run ended after {st['tick']} ticks",
              file=sys.stderr)
        return 1
    dest = os.path.join(out_dir, "serve_tiny.xplane.pb")
    with open(st["xplane"], "rb") as f, open(dest, "wb") as g:
        g.write(slim(f.read()))
    shutil.rmtree(log_dir, ignore_errors=True)
    t = tracefile.reduce_xplane(dest)
    print(json.dumps({"bytes": os.path.getsize(dest), "ticks": st["tick"],
                      **spans.reduce_spans(dest),
                      "busy_s": t["busy_s"], "window_s": t["window_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
