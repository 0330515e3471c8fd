"""BENCHMARK.json against the files it names, and the data-driven layout:
a new configuration, traffic mix or per-layer metric is found by name
from files and entries alone."""

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness as H  # noqa: E402

SPEC = H.load_json(ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    c = H.Cell(SPEC, cell)
    assert c.driver().run
    assert c.traffic["kind"] in ("requests", "messages")
    names = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer(), "every cell reports a per-layer metric"
    for m in c.per_layer():
        assert m["moves"] in names
        assert H.metric_reader(m["name"])


def test_every_config_is_used_and_has_its_file():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_devices_table_has_peaks_and_slopes():
    e = H.device_entry("TPU v5 lite")
    assert e["peaks"]["bf16_flops"] == 197e12
    assert e["peaks"]["hbm_bytes_per_s"] == 819e9
    assert e["slopes"]["xla_ns_per_iter"] > 0
    assert e["slopes"]["kernel_ns_per_iter"] > 0
    with pytest.raises(H.BenchError):
        H.device_entry("TPU v99")


def test_new_files_are_found_by_name(tmp_path):
    """A later PR adds a configuration, a traffic mix and a metric as
    files plus entries in BENCHMARK.json; no harness code changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    b = tmp_path / "bench"
    cfg = H.load_json(b / "configs" / "granite-3-2b.cord.json")
    cfg["serve"]["n_blocks"] = 160
    (b / "configs" / "granite-3-2b.small-pool.json").write_text(
        json.dumps(cfg))
    mix = H.load_json(b / "traffic" / "chat.json")
    mix["prompt"] = dict(mix["prompt"], median=128, max=512)
    (b / "traffic" / "short-chat.json").write_text(json.dumps(mix))
    (b / "metrics" / "ticks_per_s.py").write_text(
        "def read(run):\n    return run['ticks'] / 2.0\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0],
                                name="granite-3-2b.small-pool",
                                file="bench/configs/"
                                     "granite-3-2b.small-pool.json"))
    spec["workloads"].append({"name": "granite-3-2b.short-chat.small-pool",
                              "config": "granite-3-2b.small-pool",
                              "traffic": "short-chat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "granite-3-2b.chat.cord" in m["workloads"]:
            m["workloads"].append("granite-3-2b.short-chat.small-pool")
    spec["per_layer"].append({"name": "ticks_per_s", "unit": "1/s",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "serve engine", "moves": "tok_s",
                              "workloads":
                                  ["granite-3-2b.short-chat.small-pool"]})
    cell = H.Cell(spec, "granite-3-2b.short-chat.small-pool",
                  root=tmp_path)
    assert cell.config["serve"]["n_blocks"] == 160
    assert cell.traffic["prompt"]["max"] == 512
    assert cell.driver().run and cell.reference().logits_at
    got = H.read_per_layer(cell, {"ticks": 10, "trace": None,
                                  "config": cfg, "traffic": mix,
                                  "peaks": {}, "tokens": [],
                                  "traced_host": (None, None),
                                  "decode_tokens": 0, "max_batch": 8})
    assert got["ticks_per_s"] == {"value": 5.0, "unit": "1/s"}
    assert "slot_occupancy_pct" not in got       # not listed for this cell
    with pytest.raises(H.BenchError):
        H.Cell(spec, "no-such-cell", root=tmp_path)
