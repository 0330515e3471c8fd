"""The trinity-mini cell's pieces on the CPU: the program against the
configuration's float32 reference at a small size with the same layer
pattern and expert share (prefill, then paged decode through the pool),
the reference's weights against the program's parameter tree at the
published widths, the driver's configuration checks, the traffic, the
FLOP counts, and the expert layer's readers."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import flops_moe  # noqa: E402
import harness as H  # noqa: E402
import traffic as T  # noqa: E402

TRINITY = H.load_json(BENCH / "configs" / "trinity-mini.bypass.json")
MIX = H.load_json(BENCH / "traffic" / "chat-8k.json")
REF = H.load_module(BENCH / "configs" / "trinity-mini.py")
DRIVER = H.load_module(BENCH / "drivers" / "serve_moe.py")


def small_config(dtype="float32"):
    """Trinity's layer pattern and share at a CPU size: 8 layers (2 dense,
    6 MoE: two periods of 3 sliding and 1 full), window 32, 4 held of 16
    routed experts, top-4."""
    c = dict(TRINITY)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=96, moe_intermediate_size=32,
             num_hidden_layers=8, num_dense_layers=2, vocab_size=512,
             num_experts=4, num_experts_per_tok=4, sliding_window=32,
             layer_types=TRINITY["layer_types"][:8], torch_dtype=dtype)
    c["deployment"] = dict(TRINITY["deployment"], router_experts=16,
                           first_held=4)
    return c


def test_reference_weights_are_the_programs_tree():
    """At the published widths: the reference draws exactly the leaves,
    with exactly the shapes, that the program's model holds (a leaf the
    program looks for and does not find would silently change its
    maths, e.g. routing without the selection bias)."""
    import jax

    from repro.models import build_model
    cfg = DRIVER.model_config(TRINITY)
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(want)[0]}
    got = {path: shape for path, (shape, _) in REF.weight_specs(TRINITY).items()}
    assert got == flat
    n = sum(math.prod(s) for s in got.values())
    assert 3.4e9 < n < 3.5e9                 # 6.95 GB in bfloat16


def test_program_matches_reference_prefill_then_paged_decode():
    """Prefill a 40-token prompt (longer than the 32-token window), then
    decode 6 tokens through the block pool, teacher-forced; every logit
    row against the reference's full forward at the same position.
    Both sides are float32: what differs is the order of summation
    (blocked online-softmax attention, the grouped expert kernel, the
    gated sums), a few ulps of logits of about unit size, so 1e-4; an
    expert chosen differently would move a row by far more."""
    import jax
    import jax.numpy as jnp

    from repro.layers.kvcache import (
        kv_pool_init,
        kv_pool_insert,
        kv_pool_scatter_token,
    )
    from repro.models import build_model
    c = small_config()
    cfg = DRIVER.model_config(c)
    model = build_model(cfg)
    words = H.seed_words(2**33 + 1)
    weights = REF.init_weights(c, words, jax.devices()[0],
                               dtype=jnp.float32)
    p, steps, bs = 40, 6, 8
    seq = np.random.default_rng(words).integers(0, c["vocab_size"],
                                                p + steps).astype(np.int32)
    want = REF.logits_at(weights, c, seq, np.arange(p - 1, p + steps))

    cover = 48
    toks = np.zeros((1, cover), np.int32)
    toks[0, :p] = seq[:p]
    logits, cache = jax.jit(lambda w, t, cch: model.prefill(
        w, {"tokens": t}, cch, last_pos=jnp.asarray([p - 1])))(
        weights, jnp.asarray(toks), model.init_cache(1, cover))
    np.testing.assert_allclose(np.asarray(logits[0, -1]), want[0],
                               rtol=1e-4, atol=1e-4)

    kvh, hd = c["num_key_value_heads"], c["head_dim"]
    pool = kv_pool_init(c["num_hidden_layers"], 12, bs, kvh, hd,
                        dtype=jnp.float32)
    ids = np.arange(1, 1 + cover // bs, dtype=np.int32)
    pool = kv_pool_insert(pool, cache, ids, bs)
    tables = np.zeros((1, 12), np.int32)
    tables[0, :8] = np.arange(1, 9)
    active = np.ones(1, bool)

    @jax.jit
    def step(w, tok, pool, pos):
        lg, new, rows = model.decode_step_paged(w, tok, pool, tables, pos)
        return lg, kv_pool_scatter_token(pool, new, tables, pos, active,
                                         bs), rows

    for i in range(steps):
        pos = np.asarray([p + i], np.int32)
        lg, pool, rows = step(weights, jnp.asarray(seq[None, p + i:p + i + 1]),
                              pool, pos)
        np.testing.assert_allclose(np.asarray(lg[0, -1]), want[1 + i],
                                   rtol=1e-4, atol=1e-4)
        # (MoE layers, held experts); a token picks top-4 of 16
        assert rows.shape == (6, 4)
        assert 0 <= int(rows.sum()) <= 6 * 4


def test_model_config_refuses_what_the_program_does_not_run():
    for key, bad in (("model_type", "llama"), ("tie_word_embeddings", True),
                     ("score_func", "softmax"), ("route_norm", False)):
        with pytest.raises(H.BenchError, match=key):
            DRIVER.model_config(dict(TRINITY, **{key: bad}))
    far = dict(TRINITY, deployment=dict(TRINITY["deployment"],
                                        first_held=124))
    with pytest.raises(H.BenchError, match="not among"):
        DRIVER.model_config(far)
    cfg = DRIVER.model_config(TRINITY)
    assert (cfg.moe.num_experts, cfg.moe.held_experts, cfg.moe.top_k) == \
        (128, 8, 8)
    assert cfg.param_count() == pytest.approx(3.47e9, rel=0.01)


def test_chat_8k_traffic_fits_the_engine():
    e = MIX["engine"]
    assert MIX["prompt"]["max"] + MIX["output"]["max"] + 1 <= e["kv_len"]
    lens = T.lognormal_quantiles(MIX["prompt"], MIX["block"])
    assert sorted(lens)[MIX["block"] // 2] == pytest.approx(4096, rel=0.1)
    serve = DRIVER.serve
    resumes = TRINITY["serve"]["n_blocks"] * e["block_size"] < \
        e["max_batch"] * e["kv_len"]
    warm = serve.warm_lengths(MIX, resumes)
    # whole chunks from two up, then the longest re-prefill
    assert warm[0] == 1024 and warm[-1] == 7679 + 511
    assert all(b - a == 512 for a, b in zip(warm[:-2], warm[1:-1]))


def test_token_flops_and_kernel_bytes_by_hand():
    c = small_config()
    d, h, kvh, hd = 64, 4, 2, 16
    attn = d * hd * (h + 2 * kvh) + h * hd * d + d * h * hd
    moe = d * 16 + 3 * d * 32 * (1 + 4 * 4 / 16)
    weights = 8 * attn + 2 * 3 * d * 96 + 6 * moe + d * 512
    # ctx 50: 6 sliding layers see 32, 2 full layers see 50
    want = 2 * weights + 4 * h * hd * (6 * 32 + 2 * 50)
    assert flops_moe.token_flops(c, 50) == pytest.approx(want)
    # trinity-mini at a 4.7k history: about 2.4 G multiply-adds a token
    assert 4.5e9 < flops_moe.token_flops(TRINITY, 4700) < 5.0e9


def _record(trace):
    return {"config": TRINITY, "peaks": {"bf16_flops": 197e12,
                                         "hbm_bytes_per_s": 819e9},
            "tokens": [(1.0, 4000), (1.5, 4001), (2.5, 4002)],
            "traced_host": (1.2, 3.0), "trace": trace}


@pytest.mark.parametrize("chips, kernel_s, want", [(1, 0.4, 25.0),
                                                     (2, 0.6, 30.0)])
def test_expert_readers_on_a_record(chips, kernel_s, want):
    """Kernel seconds are summed over the chips' planes, decode and chunk
    programs alike, and busy time is per chip."""
    trace = {"window_s": 2.0, "busy_s": 1.6 if chips == 1 else 1.0,
             "devices": {f"/device:TPU:{i}": 1.0 for i in range(chips)},
             "ops": {"_expert_ffn_fwd.12": [kernel_s * 2 / 3, 60, 0],
                     "_expert_ffn_fwd.3": [kernel_s / 3, 30, 0],
                     "_expert_ffn_fwd": [0.0, 0, 0],
                     "fusion.1": [1.2, 10, 0]}}
    read = lambda n: H.metric_reader(n)(_record(trace))  # noqa: E731
    assert read("expert_kernel_pct.decode") == pytest.approx(want)
    want_mfu = (flops_moe.token_flops(TRINITY, 4001)
                + flops_moe.token_flops(TRINITY, 4002)) / 1.8 / 197e12
    assert read("mfu_pct.decode_moe") == pytest.approx(100 * want_mfu)


def test_expert_readers_return_nothing_without_their_source():
    """A program without the expert layer (the parent of the change that
    brings it) leaves the trace without kernel ops."""
    bare = {"window_s": 2.0, "busy_s": 1.0, "devices": {"d": 1.0},
            "ops": {"fusion.1": [1.0, 3, 0]}}
    for trace in (None, bare):
        run = _record(trace)
        assert H.metric_reader("expert_kernel_pct.decode")(run) is None
    assert H.metric_reader("mfu_pct.decode_moe")(
        dict(_record(None), traced_host=(None, None))) is None
