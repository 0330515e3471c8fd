"""Device time of the grouped expert kernel (``_expert_ffn_fwd*``, the
held experts of every MoE layer, in decode and prefill programs alike)
over device busy time, in the traced window; summed and averaged alike
over the chips used."""

import tracefile


def read(run):
    t = run.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    sec, calls, _ = tracefile.kernel_totals(t, ["_expert_ffn_fwd"])
    if not calls:
        return None
    return 100.0 * sec / len(t["devices"]) / t["busy_s"]
