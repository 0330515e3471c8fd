"""Runs of the harness without its look for a chip, on the CPU at a size
a test run holds: a sound run comes out correct; the timed path broken
underneath comes out not correct, once for each fault a cell can have;
and the controls (a served model's reference in float8 in the program's
place, deliveries with RC's in-order guarantee broken) fail."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import copy  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402

RUN = H.load_module(BENCH / "run.py")
SPEC = H.load_json(BENCH.parent / "BENCHMARK.json")
# CPU slopes: any fixed value keeps the emulated costs small and steady
SLOPES = {"xla_ns_per_iter": 50.0, "kernel_ns_per_iter": 50.0}


def small_cell(name: str, config: dict, traffic: dict):
    cell = H.Cell(SPEC, name)
    cell.config, cell.traffic = config, traffic
    return cell


def serve_cell(d=128, layers=2, vocab=512, limit=0.03):
    cell = H.Cell(SPEC, "granite-3-2b.chat.cord")
    c = copy.deepcopy(cell.config)
    c.update({"hidden_size": d, "intermediate_size": 4 * d,
              "num_hidden_layers": layers, "num_attention_heads": d // 64,
              "num_key_value_heads": 2 if d >= 256 else 1,
              "vocab_size": vocab, "embedding_multiplier": d ** 0.5})
    c["serve"]["n_blocks"] = 24
    c["check"]["gap_limit"] = limit
    m = copy.deepcopy(cell.traffic)
    m.update({"prompt": {"median": 24, "sigma": 0.9, "max": 40},
              "output": {"median": 8, "sigma": 0.5, "max": 12},
              "engine": {"max_batch": 4, "block_size": 4,
                         "prefill_chunk": 16, "temperature": 0.0,
                         "kv_len": 96},
              "backlog": 200, "check_tokens": 48})
    return small_cell("granite-3-2b.chat.cord", c, m)


def verbs_cell(iters=40):
    """The perftest RC cell, from its files (not yet in BENCHMARK.json)."""
    cell = H.Cell(SPEC, "granite-3-2b.chat.cord")
    cell.name, cell.chips = "perftest-rc.send-64B.cord", 4
    cell.config = H.load_json(BENCH / "configs" / "perftest-rc.cord.json")
    cell.config["iters"] = iters
    cell.traffic = H.load_json(BENCH / "traffic" / "send-64B.json")
    cell.workload = {"name": cell.name, "config": "perftest-rc.cord",
                     "traffic": "send-64B", "chips": 4}
    cell.spec = dict(SPEC, end_to_end=SPEC["end_to_end"] + [
        {"name": "msg_rate", "unit": "msg/s", "better": "higher",
         "bound": 0.05, "source": "host_clock"}])
    return cell


def execute(cell, seconds=2.0, seed=3, control=None):
    import jax
    ctx = types.SimpleNamespace(
        cell=cell, seed=seed, words=H.seed_words(seed), seconds=seconds,
        trace=False, devices=jax.devices()[:cell.chips], slopes=SLOPES,
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        counter=COUNTER, t_start=time.perf_counter(),
        out_dir=Path("/nonexistent"), control=control)
    if control:
        return cell.driver().run(ctx)
    return RUN.execute(ctx)


COUNTER = None


@pytest.fixture(scope="module", autouse=True)
def counter():
    global COUNTER
    COUNTER = H.CompileCounter()


# ---------------------------------------------------------------------------
# the served model
# ---------------------------------------------------------------------------

def test_serve_sound_run_is_correct():
    result, checks = execute(serve_cell())
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {
        m["name"] for m in H.Cell(SPEC, "granite-3-2b.chat.cord").end_to_end()}
    assert checks[0]["name"] == "widest_logit_gap"
    assert checks[0]["value"] <= checks[0]["limit"]


def test_serve_token_altered_where_produced(monkeypatch):
    from repro.serve import engine
    real = engine.sample

    def altered(logits, rng, temperature):
        return (real(logits, rng, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample", altered)
    result, checks = execute(serve_cell())
    assert not result["correct"], checks
    assert checks[0]["value"] > checks[0]["limit"]


def test_serve_step_returns_its_state_unchanged(monkeypatch):
    from repro.serve import engine
    # the decode step leaves the KV pool as it found it
    monkeypatch.setattr(engine, "kv_pool_scatter_token",
                        lambda pool, *a, **k: pool)
    result, checks = execute(serve_cell())
    assert not result["correct"], checks


def test_serve_float8_control_fails():
    """The reference computed in float8, its top tokens in the served
    tokens' place, comes out not correct by the run's own judgement,
    where the program's tokens of the same run come out correct (limit
    set at this size from the same two readings)."""
    out = execute(serve_cell(d=256, layers=4, vocab=8192), seconds=3.0,
                  control="float8_e4m3fn")
    assert out["verdicts"]["program"]["correct"], out["verdicts"]
    assert not out["verdicts"]["control"]["correct"], out["verdicts"]
    assert not out["correct"] and out["failed"] > 0
    assert out["checks"][0]["value"] > out["checks"][0]["limit"]


# ---------------------------------------------------------------------------
# the verbs cell
# ---------------------------------------------------------------------------

def test_verbs_sound_run_is_correct():
    result, checks = execute(verbs_cell())
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"msg_rate", "setup_s"}


def test_verbs_exchange_between_chips_left_out(monkeypatch):
    import jax
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis, perm: x)
    result, checks = execute(verbs_cell())
    assert not result["correct"], checks
    assert result["failed"] > 0


def test_verbs_answer_altered_where_produced(monkeypatch):
    from repro.core import verbs
    real = verbs.windowed_send

    def altered(*a, **k):
        out, qp, state = real(*a, **k)
        return out.at[0, 0].add(1), qp, state

    monkeypatch.setattr(verbs, "windowed_send", altered)
    result, _ = execute(verbs_cell())
    assert not result["correct"]


def test_verbs_step_returns_its_state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from repro.core import verbs
    monkeypatch.setattr(verbs, "windowed_send",
                        lambda dp, cfg, qp, msgs, *a, **k:
                        (jnp.zeros_like(msgs), qp, None))
    result, _ = execute(verbs_cell())
    assert not result["correct"]


def test_verbs_in_order_control_fails():
    out = execute(verbs_cell(), control=True)
    assert out["verdicts"]["program"]["correct"], out["verdicts"]
    assert not out["verdicts"]["control"]["correct"]
    assert not out["correct"] and out["failed"] > 0
