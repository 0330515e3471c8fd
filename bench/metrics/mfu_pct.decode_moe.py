"""Model FLOPs of the tokens emitted in the traced stretch (each at its
own context, from the configuration's shapes at this chip's expert
share: bench/flops_moe.py), over the stretch's host time, over the chip's
bf16 peak: the whole step's share of peak, as mfu_pct.decode reads it
for a dense decoder."""

import flops_moe


def read(run):
    t0, t1 = run.get("traced_host") or (None, None)
    if t0 is None or t1 is None or t1 <= t0:
        return None
    work = sum(flops_moe.token_flops(run["config"], ctx)
               for t, ctx in run["tokens"] if t0 <= t <= t1)
    if not work:
        return None
    return 100.0 * work / (t1 - t0) / run["peaks"]["bf16_flops"]
