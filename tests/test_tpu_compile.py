"""The served path compiles for a TPU v5e chip, checked here without one.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot (block shapes Mosaic refuses, lowerings it lacks) at no chip time.
The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and a worker that collects this file must
not take it from the others.

The CPU tests at the top cover the compile-cache helper and the chip smoke's
refusal to run without a TPU.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]


# --------------------------------------------------------------------- CPU


def test_compile_cache_defers_to_env(monkeypatch, tmp_path):
    from repro.launch.serve import enable_compile_cache

    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    assert enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch.serve import enable_compile_cache

    updates = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    path = str(REPO / ".jax_cache")
    assert enable_compile_cache() == path
    assert updates == [("jax_compilation_cache_dir", path)]


def test_chip_smoke_refuses_without_tpu():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


# ------------------------------------------------------------- TPU compile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("d,L", [(48, 512), (768, 1024)])
def test_ivf_scan_compiles_for_v5e(one_chip, d, L):
    """The served width (d=48, 512-row tiles) and an embedding-model width."""
    from repro.kernels.ivf_scan.ivf_scan import ivf_scan_pallas

    G, QB, C, k = 16, 8, 8, 10
    compiled = jax.jit(ivf_scan_pallas, static_argnames=("k",)).lower(
        _spec(one_chip, (G, QB, d), jnp.float32),
        _spec(one_chip, (G,), jnp.int32),
        _spec(one_chip, (C, L, d), jnp.float32),
        _spec(one_chip, (C,), jnp.int32), k=k).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_for_v5e(one_chip):
    """qwen3-1.7b decode: 16 query heads over 8 KV heads of 128, bf16."""
    from repro.kernels.decode_attention.ops import decode_attention

    B, S, H, KV, dh = 8, 4096, 16, 8, 128
    compiled = jax.jit(decode_attention, static_argnames=("impl",)).lower(
        _spec(one_chip, (B, H, dh), jnp.bfloat16),
        _spec(one_chip, (B, S, KV, dh), jnp.bfloat16),
        _spec(one_chip, (B, S, KV, dh), jnp.bfloat16),
        _spec(one_chip, (B,), jnp.int32), impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def _qwen3_programs(sharding, slots, max_len):
    """qwen3-1.7b's config, parameter and decode-state shapes on a chip."""
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("qwen3-1.7b")
    on_chip = lambda tree: jax.tree.map(
        lambda a: _spec(sharding, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(
        lambda: lm.init_decode_state(cfg, slots, max_len)))
    return cfg, params, state


def _lower_decode(sharding, cfg, params, state):
    from repro.serving.engine import _decode
    from repro.serving.sampler import SamplerConfig

    B = state["cache_len"].shape[0]
    return _decode.lower(params, state, _spec(sharding, (B,), jnp.int32),
                         _spec(sharding, (2,), jnp.uint32),
                         _spec(sharding, (B,), jnp.bool_),
                         cfg=cfg, sampler=SamplerConfig())


def test_served_model_compiles_for_v5e(one_chip):
    """The launcher's prefill and decode programs at qwen3-1.7b widths."""
    from repro.serving.engine import jit_prefill

    cfg, params, state = _qwen3_programs(one_chip, 8, 160)
    jit_prefill.lower(params, cfg, _spec(one_chip, (1, 32), jnp.int32),
                      max_len=160).compile()
    _lower_decode(one_chip, cfg, params, state).compile()


def test_decode_updates_cache_in_place_for_v5e(one_chip):
    """The served decode program (qwen3-1.7b, 16 slots x 2048 tokens) writes
    each new KV row into the donated cache: its temporaries stay below one
    layer's K cache, and nothing copies a whole stacked cache."""
    import re

    B, T = 16, 2048
    cfg, params, state = _qwen3_programs(one_chip, B, T)
    compiled = _lower_decode(one_chip, cfg, params, state).compile()
    mem = compiled.memory_analysis()
    layer_k = B * T * cfg.n_kv_heads * cfg.d_head * 2
    assert mem.temp_size_in_bytes < layer_k, mem.temp_size_in_bytes
    caches = jax.tree.leaves(state["segments"])
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in caches)
    hlo_type = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}
    whole = "|".join(
        re.escape(f"{hlo_type[a.dtype.name]}[{','.join(map(str, a.shape))}]")
        for a in caches)
    copies = re.findall(rf"= (?:{whole})\S* copy\(", compiled.as_text())
    assert not copies, copies
