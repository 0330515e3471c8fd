"""95th percentile of every gap between two consecutive tokens of one
request, both inside the window (bench/drivers/serve.py window_stats):
the end-to-end ``itl_p95_ms``, reported per layer in the cells where its
runs spread too widely to hold a bound (PERF.md §2)."""

import math


def read(run):
    v = run.get("itl_p95_ms")
    if v is None or not math.isfinite(v):
        return None
    return v
