"""Share of HBM bandwidth the dataplane kernels reach while they run:
the bytes they move (each call reads its payload and writes it back
once, counted from the operand shapes of the calls in the trace) over
their device time, over the chip's peak bandwidth.  The in-kernel delay
(the emulated syscall) is part of that time."""

import tracefile


def read(run):
    t = run.get("trace")
    names = run["config"]["dataplane"].get("kernels")
    if not t or not names:
        return None
    sec, calls, nbytes = tracefile.kernel_totals(t, names)
    if not calls or sec <= 0:
        return None
    return 100.0 * nbytes / sec / run["peaks"]["hbm_bytes_per_s"]
