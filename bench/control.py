#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 8

For each seed, one run of the cell with a short window, as the benchmark
runs it, judged twice by the run's own judgement: the program, and the
control in the program's place (for a served model, the configuration's
reference computed in float8 e4m3, its top token at each position of the
same prompts and served tokens; for the verbs cell, the deliveries with
RC's in-order guarantee broken).  One JSON line per seed with both
verdicts; the control's has to read not correct.  The benchmark's own
runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="float8_e4m3fn")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(H.ROOT / "src"))
    cell = H.Cell(H.load_json(H.ROOT / "BENCHMARK.json"), args.workload)
    H.prepare_jax()
    counter = H.CompileCounter()
    devices = H.chip_gate(cell.chips)
    entry = H.device_entry(devices[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = types.SimpleNamespace(
            cell=cell, seed=seed, words=H.seed_words(seed),
            seconds=args.seconds, trace=False, devices=devices,
            slopes=entry["slopes"], peaks=entry["peaks"], counter=counter,
            t_start=time.perf_counter(), out_dir=H.OUT_DIR / cell.name,
            control=args.control)
        out = cell.driver().run(ctx)
        print(json.dumps({"seed": seed, **out["verdicts"],
                          "limit": out["checks"][0]["limit"],
                          "rule": out["checks"][0]["rule"],
                          "e2e": out["e2e"], "window": out.get("window"),
                          "setup": out["setup"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
