# Multi-device tests need several host devices. 8 is the standard JAX test
# harness value — NOT the 512-device dry-run configuration, which is set
# exclusively inside launch/dryrun.py (see its header comment).
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import pytest  # noqa: E402

from repro.core import compat  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    return compat.make_mesh((8,), ("data",))


@pytest.fixture(scope="session")
def mesh42():
    return compat.make_mesh((4, 2), ("data", "model"))


@pytest.fixture(scope="session")
def mesh2():
    return compat.make_mesh((2,), ("rank",))



def _greedy_reference(model, params, prompts, max_new_tokens):
    """Greedy tokens for each prompt, served ALONE at batch 1:
    ``model.prefill`` over the exact prompt, then ``model.decode_step`` at
    scalar positions, with no engine, slots, buckets or scheduler — the
    plain loop the serving engine must reproduce at temperature 0."""
    import jax
    import jax.numpy as jnp

    prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c))
    step = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, c, pos))
    outs = []
    for prompt in prompts:
        toks = jnp.asarray(prompt, jnp.int32)[None]
        n = toks.shape[1]
        logits, cache = prefill(params, {"tokens": toks},
                                model.init_cache(1, n + max_new_tokens + 1))
        out = [int(logits[0, -1].argmax())]
        for i in range(max_new_tokens - 1):
            logits, cache = step(params, jnp.asarray([[out[-1]]], jnp.int32),
                                 cache, jnp.asarray(n + i, jnp.int32))
            out.append(int(logits[0, -1].argmax()))
        outs.append(out)
    return outs


@pytest.fixture(scope="session")
def greedy_reference():
    """``greedy_reference(model, params, prompts, max_new_tokens)``: one
    list of batch-1 reference tokens per prompt."""
    return _greedy_reference
