"""Model FLOPs of the tokens emitted in the traced stretch (each at its
own context, from the configuration's shapes: bench/flops.py), over the
stretch's host time, over the chip's bf16 peak."""

import flops


def read(run):
    t0, t1 = run.get("traced_host") or (None, None)
    if t0 is None or t1 is None or t1 <= t0:
        return None
    work = sum(flops.decoder_token_flops(run["config"], ctx)
               for t, ctx in run["tokens"] if t0 <= t <= t1)
    if not work:
        return None
    return 100.0 * work / (t1 - t0) / run["peaks"]["bf16_flops"]
