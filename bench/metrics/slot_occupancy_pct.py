"""Decode tokens emitted in the window over decode ticks times the
engine's slots: how full the batch ran (ticks counted by on_tick)."""


def read(run):
    if not run.get("ticks"):
        return None
    return 100.0 * run["decode_tokens"] / (run["ticks"] * run["max_batch"])
