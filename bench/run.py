#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its per-layer metrics are
found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a
stretch of the window.  The last line of standard output is the result:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
last, ``checks`` (each number compared beside its limit, also printed as
the last lines of standard error).  Without a TPU, with fewer chips than
the cell asks for, or outside a checkout of the repository, it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import harness as H  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    src = H.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: {src}/repro is missing; run from a checkout of the "
              f"repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        cell = H.Cell(H.load_json(H.ROOT / "BENCHMARK.json"), args.workload)
        H.prepare_jax()
        counter = H.CompileCounter()
        devices = H.chip_gate(cell.chips)
        entry = H.device_entry(devices[0].device_kind)
        ctx = types.SimpleNamespace(
            cell=cell, seed=args.seed, words=H.seed_words(args.seed),
            seconds=args.seconds, trace=bool(args.trace), devices=devices,
            slopes=entry["slopes"], peaks=entry["peaks"], counter=counter,
            t_start=T_START, out_dir=H.OUT_DIR / cell.name)
        result, checks = execute(ctx)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    H.emit(result, checks)
    return 0


def execute(ctx) -> tuple[dict, list]:
    """Everything after the chip gate: the cell's driver, then the result
    (end-to-end metrics, or per-layer ones with the trace's numbers)."""
    cell = ctx.cell
    out = cell.driver().run(ctx)
    device = out["device"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if ctx.trace:
        trace = out["record"]["trace"]
        if trace is None:
            raise H.BenchError("the traced run took no trace")
        result["metrics"] = H.read_per_layer(cell, out["record"])
        result["device"] = {**device, "busy_s": trace["busy_s"],
                            "window_s": trace["window_s"]}
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in out["e2e"].items() if k in units}
        result["device"] = device
    result["setup"] = out["setup"]
    if "window" in out:
        result["window"] = out["window"]
    return result, out["checks"]


if __name__ == "__main__":
    sys.exit(main())
