"""What every cell shares: the spec and its files found by name, the chip
gate, the compile cache and compile counts, the pinned delay slopes, the
per-layer metric readers, and the result line.

A configuration, a traffic mix and a per-layer metric are files of their
own (``configs/<name>.json``, ``traffic/<name>.json``,
``metrics/<name>.py``), found by the names in ``BENCHMARK.json``; the
driver that runs a configuration is ``drivers/<config["driver"]>.py``.
Adding one is adding files and entries: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import inspect
import json
import math
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed, inside the checkout (gitignored): the path is part of the
# persistent cache's key, so it must never move between runs
CACHE_DIR = ROOT / ".jax_compile_cache"
OUT_DIR = BENCH / ".out"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file, a pin
    that did not take, a compile inside the window).  No result line."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (names may hold '.' and '-')."""
    if not path.is_file():
        raise BenchError(f"no such file: {path.relative_to(ROOT)}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of the spec with its configuration, traffic and the
    metrics it reports, all resolved from files by name."""

    def __init__(self, spec: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.root = root
        self.spec = spec
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(root / self.config_entry["file"])
        self.traffic = load_json(
            root / "bench" / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])

    def _reports(self, metric: dict, e2e: set[str]) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return metric.get("moves", metric["name"]) in e2e

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"] if self._reports(m, e2e)]

    def driver(self):
        return load_module(self.root / "bench" / "drivers"
                           / f"{self.config['driver']}.py")

    def reference(self):
        """The configuration's plain reference, beside its file."""
        return load_module(self.root / "bench" / "configs"
                           / f"{self.config['reference']}.py")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def read_per_layer(cell: Cell, run: dict) -> dict:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer():
        v = metric_reader(m["name"], cell.root)(run)
        if v is not None:
            if not math.isfinite(v):
                raise BenchError(f"per-layer metric {m['name']} read {v}")
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def seed_words(seed: int, n: int = 2) -> list[int]:
    """``n`` 32-bit words from any whole-number seed (large or negative
    ones included), for numpy and for ``jax.random.key``."""
    import numpy as np
    return [int(w) for w in
            np.random.SeedSequence(seed % (1 << 64)).generate_state(n)]


# ---------------------------------------------------------------------------
# the chip, the compile cache, compile counts
# ---------------------------------------------------------------------------

def prepare_jax() -> None:
    """Call before JAX is imported: the persistent compile cache at its
    fixed path, for every program however small."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chip_gate(chips: int):
    """The devices a cell runs on; raises unless JAX found ``chips`` TPU
    chips.  There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r});"
                         f" the benchmark runs only on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included) and the
    persistent cache's hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.hits = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def device_entry(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "devices.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/devices.json "
                         f"(known: {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# pinned delay slopes
# ---------------------------------------------------------------------------

def pin_slopes(slopes: dict, backend: str) -> None:
    """Seed the program's two calibration memos with the pinned slopes,
    so no probe runs and every run compiles the same iteration counts.
    A renamed memo fails here, loudly."""
    from repro.core import techniques as tech
    from repro.kernels.dataplane import ops
    probe = inspect.signature(tech.calibrate).parameters["probe_iters"]
    tech._CALIBRATION[(backend, probe.default)] = \
        float(slopes["xla_ns_per_iter"])
    ops._KERNEL_CALIBRATION[backend] = float(slopes["kernel_ns_per_iter"])


def check_slopes(slopes: dict) -> dict:
    """After the dataplane is built: both calibrations must return the
    pinned values.  Returns what they read."""
    from repro.core import techniques as tech
    from repro.kernels.dataplane import ops
    got = {"xla_ns_per_iter": tech.calibrate(),
           "kernel_ns_per_iter": ops.kernel_calibrate()}
    for k, v in got.items():
        if v != float(slopes[k]):
            raise BenchError(f"the slope pin did not take: {k} reads {v}, "
                             f"pinned {slopes[k]}")
    return got


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def emit(result: dict, checks: list[dict]) -> None:
    """Each compared number beside its limit, last on standard error and
    last in the result line; the result is the last line of stdout."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}, "
              f"{c['rule']})", file=sys.stderr)
    result = dict(result)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


__all__ = ["BenchError", "Cell", "CompileCounter", "load_json",
           "load_module", "metric_reader", "read_per_layer", "seed_words",
           "prepare_jax", "chip_gate", "device_entry", "pin_slopes",
           "check_slopes", "device_info", "emit", "ROOT", "BENCH",
           "CACHE_DIR", "OUT_DIR"]
