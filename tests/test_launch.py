"""Launch helpers: explicit-device meshes, the compile-cache placement
rule, and the serve launcher's building blocks (the pieces
``chip_smoke.py`` drives on the chip)."""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache
from repro.launch import serve as launcher
from repro.launch.mesh import make_local_mesh


@pytest.mark.parametrize("model", [1, 2])
def test_make_local_mesh_spans_exactly_the_given_devices(model):
    devs = jax.devices()[2:6]
    mesh = make_local_mesh(devs, model=model)
    assert list(mesh.devices.flat) == devs
    assert dict(mesh.shape) == {"data": 4 // model, "model": model}


def test_compile_cache_defers_to_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []                       # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_compile_cache")


def test_serve_launcher_builds_a_mediated_engine():
    args = launcher.build_parser().parse_args(
        ["--requests", "4", "--tenants", "a,b", "--prompt-lens", "5,9",
         "--block-size", "16", "--kv-len", "100",
         "--param-dtype", "bfloat16"])
    dev = jax.devices()[1]
    cfg, model, params = launcher.load_model(args, dev)
    leaves = jax.tree.leaves(params)
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    assert all(x.devices() == {dev} for x in leaves)

    eng = launcher.build_engine(cfg, model, params, args, [dev])
    assert eng.dp.mode == "cord" and eng.dp.tenants == ("a", "b")
    assert list(eng.dp.mesh.devices.flat) == [dev]
    assert eng.scfg.kv_cache_len == 112          # a whole number of blocks

    reqs = launcher.make_requests(cfg, args)
    assert [len(r.prompt) for r in reqs] == [5, 9, 5, 9]
    assert [r.tenant for r in reqs] == ["a", "b", "a", "b"]
    assert all(r.logits is None for r in reqs)
    assert all(r.logits == [] for r in
               launcher.make_requests(cfg, args, keep_logits=True))
