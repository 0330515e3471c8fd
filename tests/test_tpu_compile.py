"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

Nothing here runs: each test lowers a kernel against a *described*
``v5e:2x2`` topology and compiles it with the TPU compiler, which
refuses what interpret mode accepts (scalar stores to VMEM, slices not
aligned to the tiling, ragged DMAs).  The payloads are the ones the
served granite-3-2b path and the calibration probe hand the dataplane
kernels, the paged decode attention at granite-3-2b's widths, and
trinity-mini's grouped expert kernel and whole paged decode step, and
granite-3-2b's whole paged decode step through a cord dataplane.

The topology is described only inside the module fixture — never while
a module is imported — because only one process at a time may load the
TPU compiler's library; the fixture skips where it cannot be described.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_model_config
from repro.configs.base import DataplaneConfig, ServeConfig
from repro.core import Dataplane
from repro.kernels.dataplane import bounce, bounce_copy, mediated_cost
from repro.kernels.expert_ffn import expert_ffn, num_tiles, tile_rows
from repro.kernels.paged_attention import paged_decode_attention
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.serve import Engine

PAYLOADS = [
    pytest.param((4, 1, 2048), jnp.bfloat16, id="decode-activation"),
    pytest.param((4, 4096, 8, 64), jnp.bfloat16, id="kv-stripe"),
    pytest.param((8193,), jnp.float32, id="ragged-f32"),
    pytest.param((256,), jnp.float32, id="calibration-probe"),
    pytest.param((49155, 2048), jnp.bfloat16, id="embed-table"),
    pytest.param((484, 16, 512), jnp.bfloat16, id="kv-pool-slice"),
    pytest.param((3, 1, 8192), jnp.bfloat16, id="mlp-hidden"),
    pytest.param((37, 384), jnp.float32, id="ragged-rows-f32"),
]

KERNELS = [
    pytest.param(lambda x: bounce_copy(x, copies=2, interpret=False),
                 id="bounce_copy"),
    pytest.param(lambda x: mediated_cost(x, 1000, copies=1,
                                         interpret=False)[0],
                 id="mediated_cost"),
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("shape,dtype", PAYLOADS)
def test_dataplane_kernel_compiles_for_v5e(one_chip, kernel, shape, dtype):
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(kernel).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_for_v5e(one_chip):
    """granite-3-2b's decode attention over its served pool: batch 3, 32
    query heads on 8 KV heads of 64, 40 layers of 484 blocks of 16,
    tables of 483, at a traced layer and window."""
    b, h, kvh, hd, bs, n_blocks, t_len = 3, 32, 8, 64, 16, 483, 483

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((40, n_blocks + 1, bs, kvh * hd), jnp.bfloat16)
    new = arg((b, kvh, hd), jnp.bfloat16)
    i32 = jnp.int32

    def step(q, k_pool, v_pool, tables, pos, k_new, v_new, layer, window):
        return paged_decode_attention(q, k_pool, v_pool, tables, pos, k_new,
                                      v_new, layer=layer, window=window,
                                      interpret=False)

    compiled = jax.jit(step).lower(
        arg((b, h, hd), jnp.bfloat16), pool, pool, arg((b, t_len), i32),
        arg((b,), i32), new, new, arg((), i32), arg((), i32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("max_rows", [
    pytest.param(16 * 8, id="decode-batch16"),
    pytest.param(512 * 8, id="prefill-chunk512"),
])
def test_expert_ffn_compiles_for_v5e(one_chip, max_rows):
    """trinity-mini's held experts (8 of width 1024 over hidden 2048) for
    a decode tick of 16 slots and a prefill chunk of 512 tokens, top-8."""
    e, d, f = 8, 2048, 1024
    tm = tile_rows(max_rows)
    m = num_tiles(max_rows, e, tm) * tm

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda x, r, wg, wi, wo: expert_ffn(
        x, r, wg, wi, wo, tm=tm, interpret=False)).lower(
        arg((m, d)), arg((e,), jnp.int32), arg((e, d, f)), arg((e, d, f)),
        arg((e, f, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_trinity_paged_decode_step_compiles_for_v5e(one_chip):
    """The engine's whole paged decode program for trinity-mini at its
    published widths and the bench cell's geometry (16 slots of 8192,
    4096 blocks of 16): both kernels inside the layer scans, and it fits
    one chip's 15.75 GiB with the weights and the pool as arguments."""
    cfg = get_model_config("trinity-mini")
    model = build_model(cfg)
    b, nb, bs = 16, 4096, 16
    serve = ServeConfig(max_batch=b, prefill_chunk=512, max_new_tokens=512,
                        kv_cache_len=8192, block_size=bs, n_blocks=nb)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: arg(s.shape, jnp.bfloat16),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = Engine(model, params, cfg, serve, eos_id=-1)
    layers, kvh, hd, dt = eng._pool_geom
    pool = {n: arg((layers, nb + 1, bs, kvh * hd), dt) for n in "kv"}
    i32 = jnp.int32
    compiled = eng._step_pool.lower(
        params, arg((b, 1), i32), pool, arg((b, eng._tables_len), i32),
        arg((b,), i32), arg((b,), bool)).compile()
    text = compiled.as_text()
    assert "_expert_ffn_fwd" in text and "_paged_fwd" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 15.75 * 2**30


def test_granite_cord_paged_decode_step_compiles_for_v5e(one_chip,
                                                        monkeypatch):
    """granite-3-2b's paged decode program at its published widths (two
    of its 40 layers) and the bench cell's geometry (3 slots of 2576,
    483 blocks of 16) through a cord dataplane running the real dataplane
    kernels: each payload enters the kernel in its own row layout, so no
    layer's MLP weight slice is copied and relaid out for a dot that
    reads a mediated activation, and the embedding table is neither
    flattened nor padded."""
    # off-TPU the kernels would select interpret mode
    monkeypatch.setattr(bounce, "_is_tpu", lambda: True)
    cfg = dataclasses.replace(get_model_config("granite-3-2b"), num_layers=2,
                              dtype="bfloat16", param_dtype="bfloat16")
    model = build_model(cfg)
    dev = next(iter(one_chip.device_set))
    dp = Dataplane(DataplaneConfig(mode="cord", emulate_costs=True,
                                   pallas_dataplane="on"),
                   mesh=make_local_mesh([dev]))
    b, nb, bs = 3, 483, 16
    serve = ServeConfig(max_batch=b, prefill_chunk=512, max_new_tokens=512,
                        kv_cache_len=2576, block_size=bs, n_blocks=nb)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: arg(s.shape, jnp.bfloat16),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = Engine(model, params, cfg, serve, dp=dp, eos_id=-1)
    layers, kvh, hd, dt = eng._pool_geom
    pool = {n: arg((layers, nb + 1, bs, kvh * hd), dt) for n in "kv"}
    i32 = jnp.int32
    text = eng._step_pool.lower(
        params, arg((b, 1), i32), pool, arg((b, eng._tables_len), i32),
        arg((b,), i32), arg((b,), bool)).compile().as_text()
    assert "_bounce_fwd" in text and "_paged_fwd" in text
    relaid = re.findall(
        r"= bf16\[1,(?:2048,8192|8192,2048)\]\S* copy\(", text)
    assert not relaid, relaid
    table = cfg.vocab_size * cfg.d_model
    pads = [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* pad\(", text)]
    assert all(n < table for n in pads), pads
