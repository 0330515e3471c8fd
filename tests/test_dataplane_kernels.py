"""Pallas dataplane kernels (kernels/dataplane, docs/kernels.md).

Everything here runs the kernels in interpret mode (CPU backend) — the
contract under test is the repo invariant: mediation changes cost and
state, never results.  Bit-identity is asserted with
``assert_array_equal``, never ``allclose``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax.sharding import PartitionSpec as P

from repro.configs.base import DataplaneConfig
from repro.core import compat
from repro.core import techniques as tech
from repro.core.chunking import chunked_psum, split_chunks
from repro.core.dataplane import Dataplane
from repro.core.policies import QoSPolicy, TelemetryPolicy
from repro.kernels.dataplane import (
    COST_COPIES,
    COST_ITERS,
    bounce_copy,
    kernel_calibrate,
    kernel_cost_totals,
    kernel_iters_for_ns,
    mediated_cost,
    rescale_iters,
    use_pallas_dataplane,
)
from repro.kernels.dataplane.bounce import _row_view


# ---------------------------------------------------------------------------
# bounce_copy ≡ staged_copy
# ---------------------------------------------------------------------------

BOUNCE_CASES = [
    # (shape, dtype, copies, chunk_elems)
    ((37,), jnp.float32, 1, 16),          # ragged tail through slot 0
    ((64, 16), jnp.uint8, 3, 256),        # byte payload, multi-pass
    ((8193,), jnp.float32, 2, 8192),      # one full chunk + 1-elem tail
    ((3, 5, 7), jnp.bfloat16, 1, 32),     # nd payload, odd extents
    ((1,), jnp.float32, 2, 8192),         # single element
    ((4096,), jnp.int32, 1, 1024),        # exact multiple: no tail path
    # row views: the payload's own (rows, width) layout, tile-aligned
    # blocks of rows, the ragged rows written in after the kernel
    ((51, 256), jnp.bfloat16, 1, 8192),   # 3 rows past the last block
    ((3, 8192), jnp.bfloat16, 2, 8192),   # one whole-array block
    ((100, 512), jnp.bfloat16, 3, 1024),  # 6 blocks of 16 + 4 rows
    ((3, 1, 32, 64), jnp.bfloat16, 1, 8192),  # trailing dims merged
    ((37, 384), jnp.float32, 2, 1024),    # ragged rows, f32 tiles
]

# payloads for the value and counter checks of both entry points:
# (shape, dtype, chunk_elems); the flat path, then row views
PAYLOADS = [
    ((5,), jnp.float32, 2),
    ((128,), jnp.float32, 32),
    ((51, 256), jnp.bfloat16, 8192),
    ((3, 8192), jnp.bfloat16, 8192),
    ((100, 512), jnp.bfloat16, 1024),
    ((3, 1, 32, 64), jnp.bfloat16, 8192),
    ((37, 384), jnp.float32, 1024),
]


def _nonfinite(shape, dtype):
    """A seeded payload with NaN, -0.0 and both infinities in it."""
    x = np.array(jax.random.normal(jax.random.PRNGKey(1), shape),
                 np.float32).reshape(-1)
    x[: 4] = [np.nan, -0.0, np.inf, -np.inf]
    x[-1] = -0.0
    return jnp.asarray(x.reshape(shape)).astype(dtype)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def test_row_view_choice():
    # the view comes from the shape alone: the fewest trailing dims that
    # make a lane-aligned width, with leading dims left over
    assert _row_view((49155, 2048), jnp.bfloat16, 8192) == (49155, 2048)
    assert _row_view((484, 16, 512), jnp.bfloat16, 8192) == (7744, 512)
    assert _row_view((3, 1, 8192), jnp.bfloat16, 8192) == (3, 8192)
    assert _row_view((3, 1, 32, 64), jnp.bfloat16, 8192) == (3, 2048)
    # flat path: 1-D, no lane-aligned split, a block too wide for VMEM
    assert _row_view((4096,), jnp.float32, 8192) is None
    assert _row_view((3, 1, 49155), jnp.float32, 8192) is None
    assert _row_view((3, 5, 7), jnp.bfloat16, 32) is None
    assert _row_view((2, 1 << 20), jnp.float32, 8192) is None


@pytest.mark.parametrize("shape,dtype,copies,chunk", BOUNCE_CASES)
def test_bounce_copy_matches_staged_copy(shape, dtype, copies, chunk):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    got = bounce_copy(x, copies=copies, chunk_elems=chunk)
    want = tech.staged_copy(x, copies=copies)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bounce_copy_zero_copies_is_identity():
    x = jnp.arange(10.0)
    assert bounce_copy(x, copies=0) is x


@pytest.mark.parametrize("shape,dtype,chunk", PAYLOADS)
def test_bounce_copy_nonfinite_payload_bit_identical(shape, dtype, chunk):
    # the in-kernel tie must survive NaN / -0.0 (a select, not arithmetic)
    x = _nonfinite(shape, dtype)
    got = bounce_copy(x, copies=2, chunk_elems=chunk)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(_bits(got), _bits(x))


# ---------------------------------------------------------------------------
# mediated_cost: delay_chain tie semantics + per-chunk counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dtype,chunk", PAYLOADS)
def test_mediated_cost_value_identical(shape, dtype, chunk):
    x = _nonfinite(shape, dtype)
    out, _ = mediated_cost(x, delay_iters=100, copies=1, chunk_elems=chunk)
    assert out.shape == x.shape and out.dtype == x.dtype
    np.testing.assert_array_equal(_bits(out), _bits(x))


@pytest.mark.parametrize("shape,dtype,chunk", PAYLOADS)
def test_mediated_cost_counters(shape, dtype, chunk):
    # the cost is accounted in chunk_elems-element chunks whatever view
    # the kernel moves the payload in: the counters are the host mirror's
    x = jnp.zeros(shape, dtype)
    out, ctrs = mediated_cost(x, delay_iters=50, copies=2, chunk_elems=chunk)
    ctrs = np.asarray(ctrs)
    n_chunks = -(-x.size // chunk)
    assert ctrs.shape == (n_chunks, 2)
    # even split rounded up: every chunk burns ceil(50 / n_chunks)
    np.testing.assert_array_equal(ctrs[:, COST_ITERS], -(-50 // n_chunks))
    np.testing.assert_array_equal(ctrs[:, COST_COPIES], 2)
    assert ctrs[:, COST_ITERS].sum() >= 50
    assert (ctrs[:, COST_ITERS].sum(), ctrs[:, COST_COPIES].sum()) == \
        kernel_cost_totals(x.size, 50, 2, chunk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_mediated_cost_no_work_shortcut():
    x = jnp.ones((8,))
    out, ctrs = mediated_cost(x, delay_iters=0, copies=0)
    assert out is x
    np.testing.assert_array_equal(np.asarray(ctrs), 0)


# ---------------------------------------------------------------------------
# backend selection + calibration plumbing
# ---------------------------------------------------------------------------

def test_use_pallas_dataplane_resolution():
    assert use_pallas_dataplane("on") is True
    assert use_pallas_dataplane("off") is False
    assert use_pallas_dataplane(True) is True
    # "auto" means TPU-only; these tests run on CPU
    assert use_pallas_dataplane("auto") is (jax.default_backend() == "tpu")
    with pytest.raises(ValueError):
        use_pallas_dataplane("maybe")


def test_calibrate_memoized_per_backend():
    tech._CALIBRATION.clear()
    a = tech.calibrate()
    assert tech._CALIBRATION  # cached
    b = tech.calibrate()
    assert a == b  # second call is a dict hit, not a re-probe
    assert tech.iters_for_ns(0) == 0
    assert tech.iters_for_ns(1e6) >= 1


def test_kernel_calibration_off_tpu_matches_xla_slope():
    # off-TPU the kernel path IS delay_chain, so the slopes coincide and
    # rescale_iters is the identity — interpret-mode tests see unchanged
    # iteration counts.
    assert kernel_calibrate() == tech.calibrate()
    assert rescale_iters(1234) == 1234
    assert rescale_iters(0) == 0
    assert kernel_iters_for_ns(0) == 0


# ---------------------------------------------------------------------------
# pipeline-level equivalence: pallas on ≡ off, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (64, 128)])
@pytest.mark.parametrize("fused", [True, False])
def test_pipeline_pallas_bit_identical(mesh8, fused, shape):
    outs, reports = {}, {}
    for pallas in ("off", "on"):
        dp = Dataplane(
            DataplaneConfig(mode="socket", emulate_costs=True,
                            pallas_dataplane=pallas, fuse_mediation=fused),
            mesh=mesh8)
        assert dp.pipeline.pallas is (pallas == "on")

        @partial(compat.shard_map, mesh=mesh8, in_specs=(P("data"), P()),
                 out_specs=(P("data"), P()))
        def f(v, rt):
            g, rt = dp.all_gather(v, "data", state=rt)
            r, rt = dp.reduce_scatter(g, "data", state=rt)
            return r, rt

        out, rt = jax.jit(f)(
            jax.random.normal(jax.random.PRNGKey(3), shape),
            dp.runtime_init())
        outs[pallas] = np.asarray(out)
        reports[pallas] = dp.runtime_report(rt)["default"]
    np.testing.assert_array_equal(outs["off"], outs["on"])
    assert reports["off"] == reports["on"]


def test_stage_names_unchanged_by_pallas(mesh8):
    # the kernel path swaps the *implementation*, never the stage list
    for pallas in ("off", "on"):
        dp = Dataplane(DataplaneConfig(mode="socket", emulate_costs=True,
                                       pallas_dataplane=pallas), mesh=mesh8)
        assert dp.pipeline.stage_names == (
            "syscall-cost", "socket-stack", "staged-copy",
            "interrupt-wait", "counter-bump")


# ---------------------------------------------------------------------------
# split_chunks padding (satellite: no more collapse-to-1)
# ---------------------------------------------------------------------------

def test_split_chunks_pads_uneven():
    x = jnp.arange(10.0).reshape(10, 1)
    chunks = split_chunks(x, 4)
    assert len(chunks) == 4
    assert all(c.shape == (3, 1) for c in chunks)
    cat = np.asarray(jnp.concatenate(chunks, axis=0))
    np.testing.assert_array_equal(cat[:10], np.asarray(x))
    np.testing.assert_array_equal(cat[10:], 0)


def test_split_chunks_even_unpadded():
    x = jnp.arange(8.0)
    chunks = split_chunks(x, 4)
    assert len(chunks) == 4 and all(c.shape == (2,) for c in chunks)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(chunks)), np.asarray(x))


def test_split_chunks_more_chunks_than_rows():
    assert len(split_chunks(jnp.ones((3, 2)), 8)) == 3


# ---------------------------------------------------------------------------
# chunk-granular wire preemption
# ---------------------------------------------------------------------------

def _preempt_dp(mesh, rates):
    pols = [TelemetryPolicy(),
            QoSPolicy(rates=rates, burst=2.0, stall_ns=1e4)]
    return Dataplane(DataplaneConfig(mode="cord"), mesh=mesh,
                     tenant="t", tenants=("t",), policies=pols)


def _run_chunked(mesh, dp, n, num_chunks):
    @partial(compat.shard_map, mesh=mesh, in_specs=(P("data"), P()),
             out_specs=(P("data"), P()))
    def f(v, rt):
        return chunked_psum(dp, v, "data", num_chunks=num_chunks, state=rt)

    out, rt = jax.jit(f)(
        jax.random.normal(jax.random.PRNGKey(4), (n, 4)),
        dp.runtime_init())
    return np.asarray(out), dp.runtime_report(rt)["t"]


def test_chunk_preemption_defers_and_stays_bit_identical(mesh8):
    # 64 rows over 8 shards = 8 rows/shard -> 8 chunks per shard
    free, rep_free = _run_chunked(mesh8, _preempt_dp(mesh8, {}), 64, 8)
    gated, rep = _run_chunked(mesh8, _preempt_dp(mesh8, {"t": 0.25}), 64, 8)
    np.testing.assert_array_equal(free, gated)
    # burst 2 + 8 * 0.25 refills = 4 issuable tokens; 8 chunks -> deferrals
    assert rep["chunks"] == 8 and rep["ops"] == 8
    assert rep["throttled"] > 0
    assert rep_free["throttled"] == 0


def test_chunk_preemption_no_double_charge(mesh8):
    # an N-chunk preempted collective must cost exactly what N
    # stage-charged plain psums cost: same throttled total, because the
    # chunk ops are issued precharged.
    _, rep_chunked = _run_chunked(
        mesh8, _preempt_dp(mesh8, {"t": 0.25}), 64, 8)

    dp = _preempt_dp(mesh8, {"t": 0.25})

    @partial(compat.shard_map, mesh=mesh8, in_specs=(P("data"), P()),
             out_specs=(P("data"), P()))
    def f(v, rt):
        outs = []
        for i in range(8):
            r, rt = dp.psum(v[i], "data", tag=f"plain{i}", state=rt)
            outs.append(r)
        return jnp.stack(outs), rt

    _, rt = jax.jit(f)(
        jax.random.normal(jax.random.PRNGKey(4), (64, 4)),
        dp.runtime_init())
    rep_plain = dp.runtime_report(rt)["t"]
    assert rep_chunked["throttled"] == rep_plain["throttled"]
    assert rep_chunked["ops"] == rep_plain["ops"]


def test_chunk_preemption_uneven_payload(mesh8):
    # 80 rows / 8 shards = 10 rows/shard, 4 chunks -> tail pad of 2 rows;
    # output must slice back to the original extent, values identical to
    # the unconstrained run
    free, _ = _run_chunked(mesh8, _preempt_dp(mesh8, {}), 80, 4)
    gated, rep = _run_chunked(mesh8, _preempt_dp(mesh8, {"t": 0.25}), 80, 4)
    assert gated.shape == (80, 4)
    np.testing.assert_array_equal(free, gated)
    assert rep["chunks"] == 4 and rep["throttled"] > 0


def test_preemption_off_when_unenforced(mesh8):
    # no rates -> no governing bucket -> ops are NOT precharged and the
    # pipeline's token-bucket stage (absent here) never runs; plain path
    dp = _preempt_dp(mesh8, {})
    out, rep = _run_chunked(mesh8, dp, 64, 8)
    assert rep["throttled"] == 0 and rep["chunks"] == 8
