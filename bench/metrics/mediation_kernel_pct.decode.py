"""Device time of the dataplane's Pallas kernels (found by the kernel
names the configuration lists), over device busy time, in the traced
window; summed and averaged alike over the chips used."""

import tracefile


def read(run):
    t = run.get("trace")
    names = run["config"]["dataplane"].get("kernels")
    if not t or not names or t["busy_s"] <= 0:
        return None
    sec, calls, _ = tracefile.kernel_totals(t, names)
    if not calls:
        return None
    return 100.0 * sec / len(t["devices"]) / t["busy_s"]
