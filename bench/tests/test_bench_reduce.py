"""The reduction from a profiler trace to busy time, kernel time and
bytes, and idle gaps, on a small trace recorded on one TPU v5 lite: one
``bench/window`` span around three calls of the dataplane cost kernel on
65 536 bfloat16 elements and three 1024 x 1024 matmuls."""

import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import tracefile  # noqa: E402

TINY = str(BENCH / "tests" / "data" / "tiny.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return tracefile.reduce_xplane(TINY)


def test_window_and_busy(tiny):
    assert tiny["window_s"] == pytest.approx(4.27488e-3)
    assert list(tiny["devices"]) == ["/device:TPU:0"]
    # union of the XLA Ops intervals inside the window
    assert tiny["busy_s"] == pytest.approx(5.357e-5)
    assert 0 < tiny["busy_s"] < tiny["window_s"]


def test_kernel_found_by_name_with_bytes_from_shapes(tiny):
    sec, calls, nbytes = tracefile.kernel_totals(tiny, ["_bounce_fwd"])
    # the first of the three calls started before the window opened
    assert calls == 2
    assert sec == pytest.approx(6.101e-6)
    # operand bf16[8,64,128] read once and written back once, per call
    assert nbytes == 2 * 2 * (8 * 64 * 128 * 2)
    assert tracefile.kernel_totals(tiny, ["no_such_kernel"]) == (0.0, 0, 0)


def test_top_ops_and_idle_gaps(tiny):
    names = [n for n, _ in tiny["device_ops"]]
    assert names[0] == "fusion"               # the matmuls
    assert "_bounce_fwd.1" in names
    gaps = dict(tiny["idle_gaps"])
    # the host waits on the device (block_until_ready) in most gaps
    assert max(gaps, key=gaps.get) == "ReadSyncFlag"
    total = sum(gaps.values())
    assert total == pytest.approx(tiny["window_s"] - tiny["busy_s"],
                                  rel=1e-3)


@pytest.mark.parametrize("text, want", [
    ('%_bounce_fwd.1 = (bf16[8,64,128]{2,1,0}, s32[2]{0}) custom-call('
     'bf16[8,64,128]{2,1,0:T(8,128)(2,1)} %bitcast.2), custom_call_target='
     '"tpu_custom_call"', 8 * 64 * 128 * 2),
    ('%c = u8[4096]{0} custom-call(u8[4096]{0} %a, f32[3,5]{1,0} %b), x=1',
     4096 + 60),
    ('%fusion.3 = f32[2,2]{1,0} fusion(f32[2,2]{1,0} %p), kind=kLoop', 0),
])
def test_operand_bytes(text, want):
    assert tracefile.operand_bytes(text) == want


def test_op_name():
    assert tracefile.op_name("%fusion.45 = f32[] fusion()") == "fusion.45"
    assert tracefile.op_name("jit_step(123)") == "jit_step(123)"


def test_host_labels_pick_shortest_covering_event():
    events = sorted([(0.0, 100.0, "outer"), (10.0, 20.0, "inner"),
                     (50.0, 60.0, "other")])
    got = tracefile._host_labels(events, [5.0, 15.0, 55.0, 150.0])
    assert got == ["outer", "inner", "other", "host idle"]


def test_self_times_subtract_nested_ops():
    evs = [(0, 100, "while"), (10, 20, "a"), (30, 60, "b"), (40, 50, "c"),
           (70, 80, "d"), (120, 130, "e")]
    assert tracefile._self_times(evs) == [50, 10, 20, 10, 10, 10]


def test_union_merges_overlaps():
    assert tracefile._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_missing_window_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tracefile.newest_xplane(str(tmp_path))
    assert os.path.getsize(TINY) < 64 * 1024
