"""The benchmark's own arithmetic on the CPU: the window cut, tok_s and
itl_p95_ms from token stamps, the chat traffic from the seed, the
model-FLOP count, and the per-layer readers on a hand-made run record."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import flops  # noqa: E402
import harness as H  # noqa: E402
import traffic as T  # noqa: E402

serve = H.load_module(BENCH / "drivers" / "serve.py")
CHAT = H.load_json(BENCH / "traffic" / "chat.json")
GRANITE = H.load_json(BENCH / "configs" / "granite-3-2b.cord.json")


def test_window_cut_tok_s_and_itl():
    t0 = 100.0
    # request A: tokens every 0.1 s from t0; B starts late, ends past cut
    a = [t0 + 0.1 * i for i in range(10)]            # 100.0 .. 100.9
    b = [t0 + 0.55, t0 + 0.75, t0 + 1.05, t0 + 1.3]
    late = [t0 + 2.0]                                 # wholly past the cut
    early = [t0 - 0.5]                                # before the window
    w = serve.window_stats([(5, a), (7, b), (3, late), (4, early)], t0, 1.0)
    assert len(w["tokens"]) == 10 + 2                 # b's last two cut off
    assert w["tok_s"] == pytest.approx(12.0)
    assert w["started"] == 2
    assert w["decode_tokens"] == 9 + 1
    gaps = [0.1] * 9 + [0.2]
    assert sorted(w["gaps"]) == pytest.approx(sorted(gaps))
    assert w["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(gaps, 95))
    # contexts: prompt length + index of the token in its request
    assert [c for _, c in w["tokens"][:3]] == [5, 6, 7]


def test_chat_traffic_is_a_function_of_the_seed():
    mix = dict(CHAT, backlog=64)
    a = T.requests(mix, H.seed_words(2**33 + 7), 49155)
    b = T.requests(mix, H.seed_words(2**33 + 7), 49155)
    c = T.requests(mix, H.seed_words(11), 49155)
    assert len(a) == 64
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new_tokens"] == y["max_new_tokens"]
               for x, y in zip(a, b))
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))
    # every block of every seed holds the same lengths, in another order
    blk = mix["block"]
    for reqs in (a, c):
        for i in range(0, 64, blk):
            part = reqs[i:i + blk]
            assert sorted(len(r["prompt"]) for r in part) == \
                T.lognormal_quantiles(mix["prompt"], blk)
            assert sorted(r["max_new_tokens"] for r in part) == \
                T.lognormal_quantiles(mix["output"], blk)
    lens = T.lognormal_quantiles(mix["prompt"], blk)
    assert min(lens) >= 1 and max(lens) <= 2048
    assert sorted(lens)[blk // 2] == pytest.approx(1020, rel=0.15)
    # the order is the mix's, not the seed's
    assert [len(x["prompt"]) for x in a] == [len(x["prompt"]) for x in c]
    assert [x["max_new_tokens"] for x in a] == \
        [x["max_new_tokens"] for x in c]
    assert [r["tenant"] for r in a[:4]] == ["alice", "bob"] * 2


def test_seed_words_take_any_whole_number():
    for s in (0, 1, 2**31 + 5, 2**40, -3):
        w = H.seed_words(s)
        assert len(w) == 2 and all(0 <= x < 2**32 for x in w)
    assert H.seed_words(5) == H.seed_words(5) != H.seed_words(6)


def test_decoder_token_flops_by_hand():
    c = {"hidden_size": 4, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 8,
         "vocab_size": 10, "num_hidden_layers": 3}
    hd = 2
    per_layer = 4 * hd * (2 + 2) + 2 * hd * 4 + 3 * 4 * 8
    want = 2 * (3 * per_layer + 4 * 10) + 4 * 3 * 2 * hd * 7
    assert flops.decoder_token_flops(c, 7) == want
    # granite-3-2b: about 2 x 2.5 B parameters per token
    g = flops.decoder_token_flops(GRANITE, 0)
    assert 4.9e9 < g < 5.2e9


def test_warm_lengths_cover_every_prefill_shape():
    # prompts 191..2048: buckets 256 and 512, then 2, 3 and 4 chunks
    assert serve.warm_lengths(CHAT, resumes=False) == \
        [256, 512, 1024, 1536, 2048]
    # a pool that preempts also re-prefills prompt + output - 1
    assert serve.warm_lengths(CHAT, resumes=True) == \
        [256, 512, 1024, 1536, 2048, 2559]


def _run_record(trace=None):
    return {"config": GRANITE, "peaks": {"bf16_flops": 197e12,
                                         "hbm_bytes_per_s": 819e9},
            "tokens": [(1.0, 100), (1.5, 101), (2.5, 102), (3.5, 103)],
            "traced_host": (1.2, 3.0), "ticks": 10, "decode_tokens": 30,
            "max_batch": 8, "itl_p95_ms": 131.5, "trace": trace}


def test_readers_on_a_record():
    trace = {"window_s": 2.0, "busy_s": 1.5,
             "devices": {"/device:TPU:0": 1.5},
             "ops": {"_bounce_fwd.3": [0.6, 100, 819e9 * 0.06],
                     "fusion.1": [0.9, 10, 0]}}
    run = _run_record(trace)
    read = lambda n: H.metric_reader(n)(run)  # noqa: E731
    assert read("device_idle_pct.decode") == pytest.approx(25.0)
    assert read("slot_occupancy_pct") == pytest.approx(37.5)
    assert read("mediation_kernel_pct.decode") == pytest.approx(40.0)
    # 0.06 s worth of peak bytes moved in 0.6 s of kernel time
    assert read("mediated_cost_roofline") == pytest.approx(10.0)
    want = (flops.decoder_token_flops(GRANITE, 101)
            + flops.decoder_token_flops(GRANITE, 102)) / 1.8 / 197e12
    assert read("mfu_pct.decode") == pytest.approx(100 * want)
    assert read("itl_p95_ms.serve") == 131.5


def test_readers_return_nothing_without_their_source():
    run = _run_record(None)
    for name in ("device_idle_pct.decode", "mediation_kernel_pct.decode",
                 "mediated_cost_roofline", "device_idle_pct.verbs",
                 "mediation_kernel_pct.verbs"):
        assert H.metric_reader(name)(run) is None
    for itl in (None, float("nan")):
        assert H.metric_reader("itl_p95_ms.serve")(
            dict(run, itl_p95_ms=itl)) is None
    bypass = dict(run, config=H.load_json(
        BENCH / "configs" / "granite-3-2b.bypass.json"),
        trace={"window_s": 1.0, "busy_s": 0.5, "devices": {"d": 0.5},
               "ops": {"fusion": [0.5, 1, 0]}})
    assert H.metric_reader("mediation_kernel_pct.decode")(bypass) is None
    assert H.metric_reader("mediated_cost_roofline")(bypass) is None
    assert not math.isnan(H.metric_reader("device_idle_pct.decode")(bypass))


def test_slope_pin_takes_and_a_lost_pin_fails():
    import jax

    from repro.core import techniques as tech
    from repro.kernels.dataplane import ops
    slopes = {"xla_ns_per_iter": 12.5, "kernel_ns_per_iter": 7.25}
    backend = jax.default_backend()
    try:
        H.pin_slopes(slopes, backend)
        assert H.check_slopes(slopes) == slopes
        assert tech.iters_for_ns(400.0) == 32
        # the memo lost (renamed, cleared): the probe runs and differs
        tech._CALIBRATION.clear()
        ops._KERNEL_CALIBRATION.clear()
        with pytest.raises(H.BenchError, match="pin did not take"):
            H.check_slopes(slopes)
    finally:
        tech._CALIBRATION.clear()
        ops._KERNEL_CALIBRATION.clear()
