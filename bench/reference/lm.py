"""Plain float32 forward pass of a dense GQA + SwiGLU decoder.

Written from the published description (Qwen3 and Phi-3 model cards and
``config.json``): token embedding; per layer RMSNorm, attention with
grouped key/value heads, optional per-head RMS norm of queries and keys
(Qwen3), rotary embedding on the two halves of each head (``rotate_half``
convention), causal softmax, output projection and residual; RMSNorm,
SwiGLU feed-forward (``silu(x W_gate) * (x W_up) W_down``) and residual;
final RMSNorm and the output projection.  No cache, no batching, no kernel:
the whole sequence is recomputed, and every matrix product runs at
``highest`` precision so that float32 means float32 on a TPU.

It runs one layer at a time, making that layer's weights from the seed
(``bench.weights``), so it fits beside nothing else on the chip.  With
``precision="fp8"`` every matrix product takes its operands rounded to
float8 e4m3 first: the control, a lower precision that must fail the
comparison.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST


def _mm(a, b, precision):
    if precision == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (S, heads, dh), positions 0..S-1."""
    S, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(0, 3))
def _block(fm, x, w, precision):
    m = dict(fm)
    S, d = x.shape
    H, KV, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, eps)
    q = _mm(h, w["wq"], precision).reshape(S, H, dh)
    k = _mm(h, w["wk"], precision).reshape(S, KV, dh)
    v = _mm(h, w["wv"], precision).reshape(S, KV, dh)
    if m["qk_norm"]:
        q, k = _rms(q, eps), _rms(k, eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, H * dh)
    x = x + _mm(a, w["wo"], precision)
    h = _rms(x, eps)
    f = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(h, w["w_up"], precision)
    return x + _mm(f, w["w_down"], precision)


@partial(jax.jit, static_argnums=0)
def _layer_weights(fm, base, i):
    return weights.layer(dict(fm), base, i)


@partial(jax.jit, static_argnums=0)
def _embed(fm, base, tokens):
    return weights.embedding(dict(fm), base)[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnums=(0, 5))
def _score(fm, base, x, pos, toks, precision):
    """At positions ``pos`` of the hidden states: the best logit, the logit
    of each token column of ``toks`` (P, C), and the arg-max token."""
    m = dict(fm)
    head = weights.lm_head(m, base).astype(jnp.float32)
    h = _rms(x[pos], m["rms_norm_eps"])
    logits = _mm(h, head, precision)
    best = logits.max(-1)
    picked = jnp.take_along_axis(logits, toks, axis=-1)
    return best, picked, jnp.argmax(logits, -1).astype(jnp.int32)


def score(m: dict, seed: int, seqs: list, *, precision: str = "f32",
          pad_to: int = 256) -> list[dict]:
    """Teacher-forced scores of token sequences.

    ``seqs``: dicts with ``tokens`` (the model's whole input, int array),
    ``positions`` (where a next token was served) and ``cands`` (P, C)
    token ids to score at those positions.  Returns per sequence ``best``
    (P,), ``picked`` (P, C) and ``argmax`` (P,).  Sequences are padded at
    the end to a multiple of ``pad_to``; causal attention keeps every
    scored position exact.
    """
    fm = weights.frozen(m)
    base = weights.base_key(seed)
    S = max(len(s["tokens"]) for s in seqs)
    S = -(-S // pad_to) * pad_to
    xs = []
    for s in seqs:
        t = np.zeros((S,), np.int32)
        t[: len(s["tokens"])] = s["tokens"]
        xs.append(_embed(fm, base, jnp.asarray(t)))
    with jax.default_matmul_precision("highest"):
        for i in range(m["num_hidden_layers"]):
            w = _layer_weights(fm, base, i)
            xs = [_block(fm, x, w, precision) for x in xs]
            del w
        out = []
        for s, x in zip(seqs, xs):
            best, picked, am = _score(fm, base, x, jnp.asarray(s["positions"]),
                                      jnp.asarray(s["cands"]), precision)
            out.append({"best": np.asarray(best), "picked": np.asarray(picked),
                        "argmax": np.asarray(am)})
    return out
