"""Unified language model over heterogeneous layer stacks.

A model is a sequence of ``Segment`` runs (see configs.base).  Within each
segment parameters are stacked on a leading layer axis and executed with
``lax.scan`` — HLO size stays O(#segments), not O(#layers), which keeps the
80-layer / 32k-seq dry-runs compilable in seconds.

Entry points (all pure functions of (params, cfg, ...)):

  init_params(cfg, key)                          -> pytree
  train_loss(params, cfg, batch)                 -> scalar loss
  prefill(params, cfg, tokens, ...)              -> (last_logits, DecodeState)
  decode_step(params, cfg, tokens, state)        -> (logits, DecodeState)
  init_decode_state(cfg, batch, max_len)         -> DecodeState (zeros)

DecodeState = {"cache_len": (B,) i32, "segments": tuple[per-seg stacked state]}
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, Segment
from repro.distributed.act_sharding import constrain
from repro.models import rglru, rwkv6
from repro.models.layers import (
    apply_attention,
    apply_cross_attention,
    apply_ffn,
    apply_mla,
    apply_norm,
    attention_init_state,
    dtype_of,
    encode_cross_kv,
    ffn_init_state,
    init_attention,
    init_cross_attention,
    init_ffn,
    init_mla,
    init_norm,
    mla_init_state,
    sinusoidal_embedding,
    _dense,
)

f32 = jnp.float32

_MIXER_INIT = {
    "attn": init_attention,
    "local_attn": init_attention,
    "encoder_attn": init_attention,
    "mla": init_mla,
    "rwkv6": rwkv6.init_timemix,
    "rglru": rglru.init_rglru,
}

# mixers whose decode state is a per-position cache.  The decode layer loop
# carries their stacked cache and each layer writes only its new row into it;
# the other mixers' small per-layer states stay the loop's xs and ys.
_CACHE_MIXERS = frozenset({"attn", "local_attn", "mla"})

_MIXER_APPLY = {
    "attn": apply_attention,
    "local_attn": apply_attention,
    "encoder_attn": apply_attention,
    "mla": apply_mla,
    "rwkv6": rwkv6.apply_timemix,
    "rglru": rglru.apply_rglru,
}


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, seg: Segment, key) -> dict:
    ks = jax.random.split(key, 6)
    p = {
        "norm1": init_norm(cfg, ks[0]),
        "mixer": _MIXER_INIT[seg.mixer](cfg, seg, ks[1]),
        "norm2": init_norm(cfg, ks[2]),
        "ffn": init_ffn(cfg, seg, ks[3]),
    }
    if seg.cross_attn:
        p["norm_x"] = init_norm(cfg, ks[4])
        p["cross"] = init_cross_attention(cfg, ks[5])
    return p


def _init_segment(cfg: ModelConfig, seg: Segment, key) -> dict:
    keys = jax.random.split(key, seg.repeat)
    return jax.vmap(lambda k: _init_layer(cfg, seg, k))(keys)


def init_params(cfg: ModelConfig, key) -> dict:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4 + len(cfg.segments) + len(cfg.encoder_segments))
    params: dict = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), f32) * 0.02).astype(dt),
        "final_norm": init_norm(cfg, ks[1]),
        "segments": tuple(
            _init_segment(cfg, seg, ks[4 + i]) for i, seg in enumerate(cfg.segments)
        ),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(ks[2], (cfg.d_model, cfg.vocab_size), dt)
    if cfg.is_encoder_decoder:
        off = 4 + len(cfg.segments)
        params["encoder"] = {
            "segments": tuple(
                _init_segment(cfg, seg, ks[off + i])
                for i, seg in enumerate(cfg.encoder_segments)
            ),
            "final_norm": init_norm(cfg, ks[3]),
        }
    return params


# ---------------------------------------------------------------------------
# Single transformer block
# ---------------------------------------------------------------------------


def _apply_block(
    cfg: ModelConfig,
    seg: Segment,
    p: dict,
    x: jax.Array,
    *,
    mode: str,
    positions,
    state: Optional[dict],
    cache_len,
    enc_out,
    max_len: int,
    layer=None,
):
    st_in = state or {}
    h = apply_norm(cfg, p["norm1"], x)
    with jax.named_scope("attn"):
        mix_out, mix_st = _MIXER_APPLY[seg.mixer](
            cfg, seg, p["mixer"], h,
            mode=mode, positions=positions, state=st_in.get("mixer"),
            cache_len=cache_len, max_len=max_len, layer=layer,
        )
    x = x + mix_out

    new_state: dict = {}
    if mix_st is not None:
        new_state["mixer"] = mix_st

    if seg.cross_attn:
        h = apply_norm(cfg, p["norm_x"], x)
        if mode == "decode":
            enc_kv = st_in["enc_kv"]
        else:
            enc_kv = encode_cross_kv(cfg, p["cross"], enc_out)
        x = x + apply_cross_attention(cfg, p["cross"], h, enc_kv)
        if mode == "prefill":
            new_state["enc_kv"] = enc_kv
        elif mode == "decode":
            new_state["enc_kv"] = enc_kv  # carried through unchanged

    h = apply_norm(cfg, p["norm2"], x)
    with jax.named_scope("mlp"):
        ffn_out, ffn_st = apply_ffn(
            cfg, seg, p["ffn"], h, state=st_in.get("ffn"), mode=mode
        )
    x = x + ffn_out
    if ffn_st is not None:
        new_state["ffn"] = ffn_st
    return x, (new_state or None)


# mixers whose apply signature accepts positions/cache_len transparently via
# **_unused kwargs (rwkv6 / rglru) vs attention family that requires them —
# _MIXER_APPLY entries all take the same kwargs, so dispatch is uniform.


def _run_segment(
    cfg: ModelConfig,
    seg: Segment,
    stacked_p: dict,
    x: jax.Array,
    *,
    mode: str,
    positions,
    stacked_state=None,
    cache_len=None,
    enc_out=None,
    max_len: int = 0,
):
    """Scan a segment's layers.  Returns (x, stacked_new_state|None)."""

    if mode == "train":

        def body(carry, lp):
            out, _ = _apply_block(
                cfg, seg, lp, carry, mode=mode, positions=positions,
                state=None, cache_len=None, enc_out=enc_out, max_len=max_len,
            )
            return out, None

        if cfg.remat:
            body = jax.checkpoint(body)
        if cfg.scan_layers:
            x, _ = lax.scan(body, x, stacked_p)
        else:
            for i in range(seg.repeat):
                lp = jax.tree.map(lambda a: a[i], stacked_p)
                x, _ = body(x, lp)
        return x, None

    if mode == "prefill":

        def body(carry, lp):
            out, st = _apply_block(
                cfg, seg, lp, carry, mode=mode, positions=positions,
                state=None, cache_len=None, enc_out=enc_out, max_len=max_len,
            )
            return out, st

        if cfg.scan_layers:
            x, states = lax.scan(body, x, stacked_p)
        else:
            sts = []
            for i in range(seg.repeat):
                lp = jax.tree.map(lambda a: a[i], stacked_p)
                x, st = body(x, lp)
                sts.append(st)
            states = jax.tree.map(lambda *a: jnp.stack(a), *sts)
        return x, states

    # decode.  A cache-keeping mixer gets its segment's whole stacked cache,
    # carried through the layer loop, and writes its one new row into it in
    # place; as scan xs/ys each layer's cache would be sliced out, written
    # back and the stack copied whole every step.
    in_place = seg.mixer in _CACHE_MIXERS
    rest = dict(stacked_state)
    cache = rest.pop("mixer") if in_place else None
    rest = rest or None

    def body(carry, inp):
        x, cache = carry
        lp, i, st = inp
        if in_place:
            st = {**(st or {}), "mixer": cache}
        out, st2 = _apply_block(
            cfg, seg, lp, x, mode=mode, positions=positions,
            state=st, cache_len=cache_len, enc_out=enc_out, max_len=max_len,
            layer=i,
        )
        if in_place:
            st2 = dict(st2)
            cache = st2.pop("mixer")
        return (out, cache), (st2 or None)

    if cfg.scan_layers:
        (x, cache), new_rest = lax.scan(
            body, (x, cache), (stacked_p, jnp.arange(seg.repeat), rest))
    else:
        sts = []
        for i in range(seg.repeat):
            lp, st = jax.tree.map(lambda a: a[i], (stacked_p, rest))
            (x, cache), st2 = body((x, cache), (lp, i, st))
            sts.append(st2)
        new_rest = jax.tree.map(lambda *a: jnp.stack(a), *sts)
    if in_place:
        return x, {**(new_rest or {}), "mixer": cache}
    return x, new_rest


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(dtype_of(cfg))
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        return constrain(x, "dp", None, None)


def _head_weights(cfg: ModelConfig, params: dict) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T.astype(dtype_of(cfg))
    return params["lm_head"]


def _encoder_forward(cfg: ModelConfig, params: dict, enc_embeds: jax.Array) -> jax.Array:
    """Stub-frontend encoder: enc_embeds (B, Se, d) precomputed frames."""
    x = enc_embeds.astype(dtype_of(cfg))
    Se = x.shape[1]
    pos = jnp.arange(Se)[None, :]
    x = x + sinusoidal_embedding(pos, cfg.d_model).astype(x.dtype)
    for seg, sp in zip(cfg.encoder_segments, params["encoder"]["segments"]):
        x, _ = _run_segment(cfg, seg, sp, x, mode="train", positions=pos)
    return apply_norm(cfg, params["encoder"]["final_norm"], x)


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def _forward(cfg, params, tokens, *, mode, prefix_embeds=None, enc_embeds=None,
             max_len=0):
    """Shared train/prefill trunk.  Returns (h, states, n_prefix)."""
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    n_prefix = 0
    if cfg.n_prefix_embeds and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        n_prefix = prefix_embeds.shape[1]
    St = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(St)[None, :], (B, St))
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)

    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encoder_forward(cfg, params, enc_embeds)

    states = []
    for seg, sp in zip(cfg.segments, params["segments"]):
        x, st = _run_segment(
            cfg, seg, sp, x, mode=mode, positions=positions,
            enc_out=enc_out, max_len=max_len,
        )
        states.append(st)
    h = apply_norm(cfg, params["final_norm"], x)
    return h, states, n_prefix


def _chunked_xent(cfg: ModelConfig, h: jax.Array, w_head: jax.Array,
                  labels: jax.Array) -> jax.Array:
    """Cross-entropy without materialising (B, S, V) logits: scan over
    sequence chunks, rematerialised in backward."""
    B, S, d = h.shape
    ck = min(cfg.loss_chunk, S)
    n = -(-S // ck)
    pad = n * ck - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hs = h.reshape(B, n, ck, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, ck).transpose(1, 0, 2)

    def body(acc, inp):
        hc, lc = inp
        logits = (hc @ w_head).astype(f32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(f32)
        nll = (lse - tgt) * mask
        return (acc[0] + nll.sum(), acc[1] + mask.sum()), None

    body = jax.checkpoint(body)
    (tot, cnt), _ = lax.scan(body, (jnp.zeros((), f32), jnp.zeros((), f32)), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0)


def train_loss(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """batch: tokens (B,S) i32, labels (B,S) i32 (-1 = masked),
    optional prefix_embeds (B,P,d) [vlm], enc_embeds (B,Se,d) [audio]."""
    h, _, n_prefix = _forward(
        cfg, params, batch["tokens"], mode="train",
        prefix_embeds=batch.get("prefix_embeds"),
        enc_embeds=batch.get("enc_embeds"),
    )
    labels = batch["labels"]
    if n_prefix:
        h = h[:, n_prefix:, :]
    return _chunked_xent(cfg, h, _head_weights(cfg, params), labels)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, *, max_len: int,
            prefix_embeds=None, enc_embeds=None):
    """Returns (last_token_logits (B, V), DecodeState)."""
    B, S = tokens.shape
    h, states, n_prefix = _forward(
        cfg, params, tokens, mode="prefill",
        prefix_embeds=prefix_embeds, enc_embeds=enc_embeds, max_len=max_len,
    )
    with jax.named_scope("head"):
        logits = (h[:, -1, :] @ _head_weights(cfg, params)).astype(f32)
    state = {
        "cache_len": jnp.full((B,), S + n_prefix, jnp.int32),
        "segments": tuple(states),
    }
    return logits, state


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array, state: dict):
    """tokens: (B,) i32 new token per sequence.  Returns (logits (B,V), state)."""
    B = tokens.shape[0]
    cache_len = state["cache_len"]
    x = _embed(cfg, params, tokens[:, None])
    positions = cache_len[:, None]
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)

    new_states = []
    for seg, sp, st in zip(cfg.segments, params["segments"], state["segments"]):
        x, st2 = _run_segment(
            cfg, seg, sp, x, mode="decode", positions=positions,
            stacked_state=st, cache_len=cache_len,
        )
        new_states.append(st2)
    h = apply_norm(cfg, params["final_norm"], x)
    with jax.named_scope("head"):
        logits = (h[:, 0, :] @ _head_weights(cfg, params)).astype(f32)
    return logits, {"cache_len": cache_len + 1, "segments": tuple(new_states)}


# ---------------------------------------------------------------------------
# Decode-state construction without running prefill (dry-run / serving slabs)
# ---------------------------------------------------------------------------


def _layer_state_skeleton(cfg: ModelConfig, seg: Segment, batch: int, max_len: int):
    st: dict = {}
    if seg.mixer in ("attn", "local_attn"):
        st["mixer"] = attention_init_state(cfg, seg, batch, max_len)
    elif seg.mixer == "mla":
        st["mixer"] = mla_init_state(cfg, batch, max_len)
    elif seg.mixer == "rwkv6":
        st["mixer"] = rwkv6.timemix_init_state(cfg, batch)
    elif seg.mixer == "rglru":
        st["mixer"] = rglru.rglru_init_state(cfg, batch)
    if seg.cross_attn:
        st["enc_kv"] = {
            "k": jnp.zeros((batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.d_head), dtype_of(cfg)),
            "v": jnp.zeros((batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.d_head), dtype_of(cfg)),
        }
    fst = ffn_init_state(cfg, seg, batch)
    if fst is not None:
        st["ffn"] = fst
    return st


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      filled: int = 0) -> dict:
    """Zero decode state with capacity ``max_len`` and ``filled`` tokens."""
    segs = []
    for seg in cfg.segments:
        one = _layer_state_skeleton(cfg, seg, batch, max_len)
        stacked = jax.tree.map(
            lambda a: jnp.zeros((seg.repeat,) + a.shape, a.dtype), one
        )
        segs.append(stacked)
    return {
        "cache_len": jnp.full((batch,), filled, jnp.int32),
        "segments": tuple(segs),
    }
