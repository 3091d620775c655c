"""Random weights of a dense GQA + SwiGLU model, made from the seed.

Every weight is drawn from a key of its own, ``(seed, leaf, layer)``, so the
plain reference can make one layer at a time and get exactly the values the
program serves.  The program's weights are made on the device in one jitted
call, in the dtype they are served in.  Scales follow the usual init: the
embedding at 0.02, each projection at ``1 / sqrt(fan_in)``, norms at one.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench import seeds

def shapes(m: dict) -> dict:
    d, h, kv, dh, ff = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    return {"wq": (d, h * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
            "wo": (h * dh, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}


def _draw(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _dtype(m: dict):
    return jnp.dtype(m["torch_dtype"])


def base_key(seed: int) -> jax.Array:
    return seeds.key(seed, seeds.WEIGHTS)


def layer(m: dict, base: jax.Array, i) -> dict:
    """Projection weights of layer ``i`` (traceable in ``base`` and ``i``)."""
    out = {}
    for n, (name, shape) in enumerate(shapes(m).items()):
        k = jax.random.fold_in(jax.random.fold_in(base, n), i)
        out[name] = _draw(k, shape, 1.0 / math.sqrt(shape[0]), _dtype(m))
    return out


def embedding(m: dict, base: jax.Array) -> jax.Array:
    k = jax.random.fold_in(base, 100)
    return _draw(k, (m["vocab_size"], m["hidden_size"]), 0.02, _dtype(m))


def lm_head(m: dict, base: jax.Array) -> jax.Array:
    """(d, V) output projection; the embedding's transpose when tied."""
    if m["tie_word_embeddings"]:
        return embedding(m, base).T
    k = jax.random.fold_in(base, 101)
    return _draw(k, (m["hidden_size"], m["vocab_size"]),
                 1.0 / math.sqrt(m["hidden_size"]), _dtype(m))


def _program_params(m: dict, base: jax.Array) -> dict:
    """The tree ``repro.models.lm`` serves: one segment of stacked layers."""
    dt = _dtype(m)
    L, d, dh = m["num_hidden_layers"], m["hidden_size"], m["head_dim"]
    st = jax.vmap(lambda i: layer(m, base, i))(jnp.arange(L))
    mixer = {"wq": st["wq"], "wk": st["wk"], "wv": st["wv"], "wo": st["wo"]}
    if m["qk_norm"]:
        mixer["q_norm"] = jnp.ones((L, dh), dt)
        mixer["k_norm"] = jnp.ones((L, dh), dt)
    params = {
        "embed": embedding(m, base),
        "final_norm": {"scale": jnp.ones((d,), dt)},
        "segments": ({
            "norm1": {"scale": jnp.ones((L, d), dt)},
            "mixer": mixer,
            "norm2": {"scale": jnp.ones((L, d), dt)},
            "ffn": {"w1": st["w_gate"], "w3": st["w_up"], "w2": st["w_down"]},
        },),
    }
    if not m["tie_word_embeddings"]:
        params["lm_head"] = lm_head(m, base)
    return params


def frozen(m: dict) -> tuple:
    """The model's scalar settings as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def program_params(m: dict, seed: int) -> dict:
    """All weights, on the device, in one jitted call."""
    return _jit_params(frozen(m), base_key(seed))


@partial(jax.jit, static_argnums=0)
def _jit_params(fm, base):
    return _program_params(dict(fm), base)
