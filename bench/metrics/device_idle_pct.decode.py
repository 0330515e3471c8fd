"""Share of the traced window in which the device ran no op: 1 minus the
union of op intervals over the window, the union averaged over the
chips used (bench/tracefile.py)."""


def read(run):
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
