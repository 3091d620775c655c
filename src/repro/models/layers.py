"""Layer library: norms, RoPE, attention family (GQA/MLA/local), FFN family
(SwiGLU/GeGLU/GELU/RWKV channel-mix/MoE).

Conventions
-----------
* Parameters are plain nested dicts of ``jnp`` arrays (no flax).
* Every mixer/ffn exposes ``init_*(cfg, seg, key) -> params`` and an apply
  function.  Apply functions are mode-polymorphic:

    mode='train'    full sequence, no state
    mode='prefill'  full sequence, returns a decode state
    mode='decode'   one new token per sequence, consumes + returns state

* Attention is computed with a FLOPs-exact blocked online-softmax jnp path
  (static python loop over query chunks with statically-sliced KV ranges) so
  that causal attention costs ~S^2/2 instead of S^2 and peak memory stays
  O(B*H*qc*S).  The Pallas decode kernel (kernels/decode_attention) plugs in
  behind the same signature on TPU.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, Segment
from repro.distributed.act_sharding import constrain

Params = dict
f32 = jnp.float32


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, key, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), dtype_of(cfg)), "bias": jnp.zeros((d,), dtype_of(cfg))}
    return {"scale": jnp.ones((d,), dtype_of(cfg))}


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(f32)
    if cfg.norm_type == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
        return (y * p["scale"].astype(f32) + p["bias"].astype(f32)).astype(x.dtype)
    var = (xf**2).mean(-1, keepdims=True)
    y = xf * lax.rsqrt(var + cfg.norm_eps)
    return (y * p["scale"].astype(f32)).astype(x.dtype)


def rms_norm_headwise(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (Qwen3)."""
    xf = x.astype(f32)
    y = xf * lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(f32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Positional embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(d: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=f32) / d))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, d); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (d/2,)
    angles = positions[..., :, None].astype(f32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions: jax.Array, d: int) -> jax.Array:
    """(..., S) -> (..., S, d) classic transformer sinusoids."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=f32) / max(half - 1, 1))
    ang = positions[..., None].astype(f32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Blocked attention core (jnp online-softmax; FLOPs-exact causal blocking)
# ---------------------------------------------------------------------------


def _sdpa_block(q, k, v, mask, scale):
    """q:(B,Sq,H,dh) k,v:(B,Sk,KV,dh) mask:(B?,Sq,Sk) or None -> (B,Sq,H,dh)."""
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.reshape(B, Sq, KV, G, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf.astype(f32), k.astype(f32)) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(f32))
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    q_chunk: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Chunked attention with static causal/window KV slicing (prefill/train).

    q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh).  q_offset: absolute position of
    q[0] within the kv sequence (0 for self-attention from scratch).
    Causal chunking slices KV to [lo, hi) with *python-int* bounds, so HLO
    FLOPs match the true causal cost (~1/2 of full) instead of mask-and-waste.
    """
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    if not causal:
        return _sdpa_block(q, k, v, None, scale)

    qc = min(q_chunk, Sq)
    n_chunks = (Sq + qc - 1) // qc
    outs = []
    for i in range(n_chunks):
        q0, q1 = i * qc, min((i + 1) * qc, Sq)
        qi = q[:, q0:q1]
        hi = min(q_offset + q1, Sk)  # static upper causal bound
        lo = 0
        if window:
            lo = max(0, q_offset + q0 - window + 1)
        ki, vi = k[:, lo:hi], v[:, lo:hi]
        # in-block causal/window mask
        qpos = q_offset + jnp.arange(q0, q1)
        kpos = jnp.arange(lo, hi)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        outs.append(_sdpa_block(qi, ki, vi, mask[None], scale))
    return jnp.concatenate(outs, axis=1)


def decode_attention_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    window: int = 0,
) -> jax.Array:
    """Single-token attention against a (B, Smax, KV, dh) cache.

    cache_len: (B,) number of valid positions per sequence.  This is the pure
    jnp oracle that the Pallas decode kernel must match.
    """
    B, Smax, KV, dh = k_cache.shape
    H = q.shape[2]
    scale = 1.0 / math.sqrt(dh)
    G = H // KV
    qf = q.reshape(B, KV, G, dh).astype(f32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(f32)) * scale
    pos = jnp.arange(Smax)[None, :]
    valid = pos < cache_len[:, None]
    if window:
        valid &= pos >= (cache_len[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(f32))
    return out.reshape(B, 1, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Dense / local attention mixer (GQA, optional qk-norm, optional bias)
# ---------------------------------------------------------------------------


def _dense(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, f32) * scale).astype(dtype)


def init_attention(cfg: ModelConfig, seg: Segment, key) -> Params:
    dt = dtype_of(cfg)
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 8)
    p = {
        "wq": _dense(ks[0], (d, H * dh), dt),
        "wk": _dense(ks[1], (d, KV * dh), dt),
        "wv": _dense(ks[2], (d, KV * dh), dt),
        "wo": _dense(ks[3], (H * dh, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dt)
        p["bk"] = jnp.zeros((KV * dh,), dt)
        p["bv"] = jnp.zeros((KV * dh,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions):
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q.reshape(B, S, H, dh), "dp", None, "tp", None)
    k = constrain(k.reshape(B, S, KV, dh), "dp", None, "tp", None)
    v = constrain(v.reshape(B, S, KV, dh), "dp", None, "tp", None)
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"])
        k = rms_norm_headwise(k, p["k_norm"])
    if cfg.pos_emb == "rope" and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_init_state(cfg: ModelConfig, seg: Segment, batch: int, max_len: int):
    """Decode-state skeleton (zeros) for one attention layer.

    The cache is heads-major, (B, KV, T, dh): the layout decode attention's
    contraction reads, so a step reads each layer's cache as it lies.
    """
    dt = dtype_of(cfg)
    KV, dh = cfg.n_kv_heads, cfg.d_head
    if seg.mixer == "local_attn":
        max_len = min(max_len, cfg.local_window)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, KV, max_len, dh), jnp.int8),
            "v": jnp.zeros((batch, KV, max_len, dh), jnp.int8),
            "k_scale": jnp.zeros((batch, KV, max_len), f32),
            "v_scale": jnp.zeros((batch, KV, max_len), f32),
        }
    return {
        "k": jnp.zeros((batch, KV, max_len, dh), dt),
        "v": jnp.zeros((batch, KV, max_len, dh), dt),
    }


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (B, S, KV, dh) -> (int8 values, per-(token, head) f32 scale)."""
    amax = jnp.max(jnp.abs(x.astype(f32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(f32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dt) -> jax.Array:
    return (q.astype(f32) * scale[..., None]).astype(dt)


def apply_attention(
    cfg: ModelConfig,
    seg: Segment,
    p: Params,
    x: jax.Array,
    *,
    mode: str,
    positions: jax.Array,
    state: Optional[Params] = None,
    cache_len: Optional[jax.Array] = None,
    max_len: int = 0,
    layer=None,
):
    """Returns (out, new_state).  In decode, ``state`` is the segment's
    stacked cache and ``layer`` this layer's index in it."""
    B, S, _ = x.shape
    H, dh = cfg.n_heads, cfg.d_head
    window = cfg.local_window if seg.mixer == "local_attn" else 0
    causal = seg.mixer != "encoder_attn"
    q, k, v = _qkv(cfg, p, x, positions)

    if mode == "train":
        out = blocked_attention(q, k, v, causal=causal, window=window, q_chunk=cfg.attn_q_chunk)
        out = constrain(out, "dp", None, "tp", None)
        return constrain(out.reshape(B, S, H * dh) @ p["wo"], "dp", None, None), None

    int8_kv = cfg.kv_cache_dtype == "int8"

    if mode == "prefill":
        out = blocked_attention(q, k, v, causal=causal, window=window, q_chunk=cfg.attn_q_chunk)
        out = constrain(out, "dp", None, "tp", None)
        kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # heads-major
        if window:
            # keep only the trailing window in the ring cache
            pad = max(0, window - S)
            kw = jnp.pad(kt[:, :, -window:], ((0, 0), (0, 0), (pad, 0), (0, 0)))
            vw = jnp.pad(vt[:, :, -window:], ((0, 0), (0, 0), (pad, 0), (0, 0)))
            st = {"k": kw.astype(k.dtype), "v": vw.astype(v.dtype)}
        else:
            pad = max_len - S
            st = {
                "k": jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0))),
                "v": jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0))),
            }
        if int8_kv:
            kq, ks = _quantize_kv(st["k"])
            vq, vs = _quantize_kv(st["v"])
            st = {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
        return out.reshape(B, S, H * dh) @ p["wo"], st

    # decode: S == 1.  ``state`` is the segment's stacked cache
    # (L, B, KV, T, ...) and ``layer`` this layer's index in it: the new row
    # goes in place at [layer, b, :, slot[b]], then attention reads the
    # layer's slice.
    assert state is not None and cache_len is not None and layer is not None
    slot = (cache_len % window) if window else cache_len  # ring or append
    # a full ring holds exactly the last ``window`` positions
    eff_len = jnp.minimum(cache_len + 1, window) if window else cache_len + 1
    if int8_kv:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new = {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    else:
        new = {"k": k, "v": v}
    # one (dh,) row per sequence and head: the stack keeps its heads-major
    # layout through the scatter
    at = (layer, jnp.arange(B)[:, None], jnp.arange(cfg.n_kv_heads)[None, :],
          slot[:, None])
    st = {n: _write_rows(state[n], at, r[:, 0]) for n, r in new.items()}
    cur = {n: lax.dynamic_index_in_dim(a, layer, keepdims=False)
           for n, a in st.items()}
    if int8_kv:
        k_full = _dequantize_kv(cur["k"], cur["k_scale"], k.dtype)
        v_full = _dequantize_kv(cur["v"], cur["v_scale"], v.dtype)
    else:
        k_full, v_full = cur["k"], cur["v"]
    out = decode_attention_ref(q, jnp.swapaxes(k_full, 1, 2),
                               jnp.swapaxes(v_full, 1, 2), eff_len)
    return out.reshape(B, S, H * dh) @ p["wo"], st


def _write_rows(stack: jax.Array, at: tuple, rows: jax.Array) -> jax.Array:
    """Write one new row per sequence into a stacked cache: ``at`` indexes
    the layer, the sequences and each one's position.

    One scatter into the stack: carried through the decode layer loop and
    donated by the caller, it updates the cache in place.
    """
    with jax.named_scope("kv_write"):
        return stack.at[at].set(rows.astype(stack.dtype),
                                indices_are_sorted=True, unique_indices=True)


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------


def init_cross_attention(cfg: ModelConfig, key) -> Params:
    dt = dtype_of(cfg)
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense(ks[0], (d, H * dh), dt),
        "wk": _dense(ks[1], (d, KV * dh), dt),
        "wv": _dense(ks[2], (d, KV * dh), dt),
        "wo": _dense(ks[3], (H * dh, d), dt),
    }


def apply_cross_attention(cfg: ModelConfig, p: Params, x, enc_kv):
    """enc_kv: dict with 'k','v' (B, Senc, KV, dh) precomputed from encoder."""
    B, S, _ = x.shape
    H, dh = cfg.n_heads, cfg.d_head
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    out = _sdpa_block(q, enc_kv["k"], enc_kv["v"], None, 1.0 / math.sqrt(dh))
    return out.reshape(B, S, H * dh) @ p["wo"]


def encode_cross_kv(cfg: ModelConfig, p: Params, enc_out: jax.Array) -> Params:
    B, Se, _ = enc_out.shape
    KV, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": (enc_out @ p["wk"]).reshape(B, Se, KV, dh),
        "v": (enc_out @ p["wv"]).reshape(B, Se, KV, dh),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(cfg: ModelConfig, seg: Segment, key) -> Params:
    dt = dtype_of(cfg)
    d, H = cfg.d_model, cfg.n_heads
    r, rp, np_, vd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p: Params = {}
    if cfg.q_lora_rank:
        p["wq_a"] = _dense(ks[0], (d, cfg.q_lora_rank), dt)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dt)
        p["wq_b"] = _dense(ks[1], (cfg.q_lora_rank, H * (np_ + rp)), dt)
    else:
        p["wq"] = _dense(ks[0], (d, H * (np_ + rp)), dt)
    p["wkv_a"] = _dense(ks[2], (d, r + rp), dt)
    p["kv_norm"] = jnp.ones((r,), dt)
    p["wk_b"] = _dense(ks[3], (r, H * np_), dt)
    p["wv_b"] = _dense(ks[4], (r, H * vd), dt)
    p["wo"] = _dense(ks[5], (H * vd, d), dt)
    return p


def mla_init_state(cfg: ModelConfig, batch: int, max_len: int):
    """Latent cache: ``ckv`` (B, T, r) and ``kpe`` (B, rope_dim, T), the rope
    part stored position-minor so decode's contraction reads it as it lies."""
    dt = dtype_of(cfg)
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "kpe": jnp.zeros((batch, cfg.rope_head_dim, max_len), dt),
    }


def _mla_q(cfg: ModelConfig, p: Params, x, positions):
    B, S, _ = x.shape
    H, rp, np_ = cfg.n_heads, cfg.rope_head_dim, cfg.nope_head_dim
    if cfg.q_lora_rank:
        qa = x @ p["wq_a"]
        qa = rms_norm_headwise(qa, p["q_norm"])
        q = (qa @ p["wq_b"]).reshape(B, S, H, np_ + rp)
    else:
        q = (x @ p["wq"]).reshape(B, S, H, np_ + rp)
    q_nope, q_pe = q[..., :np_], q[..., np_:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _mla_kv_latent(cfg: ModelConfig, p: Params, x, positions):
    r, rp = cfg.kv_lora_rank, cfg.rope_head_dim
    kv = x @ p["wkv_a"]
    ckv, kpe = kv[..., :r], kv[..., r:]
    ckv = rms_norm_headwise(ckv, p["kv_norm"])
    kpe = apply_rope(kpe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, kpe


def apply_mla(
    cfg: ModelConfig,
    seg: Segment,
    p: Params,
    x: jax.Array,
    *,
    mode: str,
    positions,
    state=None,
    cache_len=None,
    max_len: int = 0,
    layer=None,
):
    B, S, _ = x.shape
    H = cfg.n_heads
    r, rp, np_, vd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    q_nope, q_pe = _mla_q(cfg, p, x, positions)
    ckv, kpe = _mla_kv_latent(cfg, p, x, positions)

    if mode in ("train", "prefill"):
        # expand per-head K/V from the latent (standard prefill path)
        k_nope = constrain((ckv @ p["wk_b"]).reshape(B, S, H, np_), "dp", None, "tp", None)
        v = constrain((ckv @ p["wv_b"]).reshape(B, S, H, vd), "dp", None, "tp", None)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(kpe[:, :, None, :], (B, S, H, rp))], -1)
        q = constrain(jnp.concatenate([q_nope, q_pe], -1), "dp", None, "tp", None)
        # pad v's head dim so the blocked kernel sees equal d; slice after
        vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, np_ + rp - vd)))
        out = blocked_attention(q, k, vpad, causal=True, q_chunk=cfg.attn_q_chunk)
        out = out[..., :vd]
        y = out.reshape(B, S, H * vd) @ p["wo"]
        st = None
        if mode == "prefill":
            pad = max_len - S
            st = {
                "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
                "kpe": jnp.pad(jnp.swapaxes(kpe, 1, 2), ((0, 0), (0, 0), (0, pad))),
            }
        return y, st

    # decode: absorbed formulation — attention in latent space, no per-head
    # K/V materialisation.  scores = q_nope @ Wk_b^T(head) @ ckv + q_pe @ kpe
    # ``state`` is the segment's stacked latent cache, written in place at
    # position cache_len[b] of [layer, b] as in apply_attention
    assert state is not None and layer is not None
    b = jnp.arange(B)[:, None]
    st = {"ckv": _write_rows(state["ckv"], (layer, b, cache_len[:, None]), ckv),
          "kpe": _write_rows(state["kpe"], (layer, b, jnp.arange(rp)[None, :],
                                            cache_len[:, None]), kpe[:, 0])}
    ckv_c, kpe_c = (lax.dynamic_index_in_dim(st[n], layer, keepdims=False)
                    for n in ("ckv", "kpe"))
    wk_b = p["wk_b"].reshape(r, H, np_)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(f32), wk_b.astype(f32))  # (B,1,H,r)
    scores = jnp.einsum("bshr,btr->bhst", q_lat, ckv_c.astype(f32))
    scores += jnp.einsum("bshp,bpt->bhst", q_pe.astype(f32), kpe_c.astype(f32))
    scores *= 1.0 / math.sqrt(np_ + rp)
    Smax = ckv_c.shape[1]
    valid = jnp.arange(Smax)[None, :] < (cache_len + 1)[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    pattn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", pattn, ckv_c.astype(f32))  # latent ctx
    wv_b = p["wv_b"].reshape(r, H, vd)
    out = jnp.einsum("bshr,rhv->bshv", ctx, wv_b.astype(f32)).astype(x.dtype)
    y = out.reshape(B, S, H * vd) @ p["wo"]
    return y, st


# ---------------------------------------------------------------------------
# FFN family
# ---------------------------------------------------------------------------


def _act(cfg: ModelConfig, x):
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def init_ffn(cfg: ModelConfig, seg: Segment, key) -> Params:
    dt = dtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if seg.ffn in ("swiglu", "geglu"):
        return {
            "w1": _dense(ks[0], (d, cfg.d_ff), dt),
            "w3": _dense(ks[1], (d, cfg.d_ff), dt),
            "w2": _dense(ks[2], (cfg.d_ff, d), dt),
        }
    if seg.ffn == "gelu_mlp":
        return {
            "w1": _dense(ks[0], (d, cfg.d_ff), dt),
            "b1": jnp.zeros((cfg.d_ff,), dt),
            "w2": _dense(ks[1], (cfg.d_ff, d), dt),
            "b2": jnp.zeros((d,), dt),
        }
    if seg.ffn == "rwkv_cmix":
        return {
            "mu_k": jnp.full((d,), 0.5, dt),
            "mu_r": jnp.full((d,), 0.5, dt),
            "wk": _dense(ks[0], (d, cfg.d_ff), dt),
            "wv": _dense(ks[1], (cfg.d_ff, d), dt),
            "wr": _dense(ks[2], (d, d), dt),
        }
    if seg.ffn == "moe":
        return init_moe(cfg, key)
    raise ValueError(seg.ffn)


def apply_ffn(cfg: ModelConfig, seg: Segment, p: Params, x, *, state=None, mode="train"):
    """Returns (out, new_state) — state only used by rwkv_cmix token shift."""
    if seg.ffn in ("swiglu", "geglu"):
        gate = _act(cfg, x @ p["w1"]) if seg.ffn == "swiglu" else jax.nn.gelu(x @ p["w1"])
        h = constrain(gate * (x @ p["w3"]), "dp", None, "tp")
        return constrain(h @ p["w2"], "dp", None, None), None
    if seg.ffn == "gelu_mlp":
        h = constrain(jax.nn.gelu(x @ p["w1"] + p["b1"]), "dp", None, "tp")
        return constrain(h @ p["w2"] + p["b2"], "dp", None, None), None
    if seg.ffn == "rwkv_cmix":
        if mode == "decode":
            prev = state  # (B, 1, d) last input
            xs = prev
        else:
            xs = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        xk = x + (xs - x) * p["mu_k"]
        xr = x + (xs - x) * p["mu_r"]
        k = jnp.square(jax.nn.relu(xk @ p["wk"]))
        out = jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"])
        new_state = x[:, -1:, :]
        return out, new_state
    if seg.ffn == "moe":
        return apply_moe(cfg, p, x), None
    raise ValueError(seg.ffn)


def ffn_init_state(cfg: ModelConfig, seg: Segment, batch: int):
    if seg.ffn == "rwkv_cmix":
        return jnp.zeros((batch, 1, cfg.d_model), dtype_of(cfg))
    return None


# ---------------------------------------------------------------------------
# MoE: top-k routing with capacity-based scatter dispatch (static shapes,
# expert dim shardable -> XLA emits all-to-all under pjit)
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key) -> Params:
    dt = dtype_of(cfg)
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 8)
    p = {
        "router": _dense(ks[0], (d, E), jnp.float32),
        "w1": _dense(ks[1], (E, d, ff), dt),
        "w3": _dense(ks[2], (E, d, ff), dt),
        "w2": _dense(ks[3], (E, ff, d), dt),
    }
    if cfg.n_shared_experts:
        sf = ff * cfg.n_shared_experts
        p["sw1"] = _dense(ks[4], (d, sf), dt)
        p["sw3"] = _dense(ks[5], (d, sf), dt)
        p["sw2"] = _dense(ks[6], (sf, d), dt)
    return p


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


def apply_moe(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """Top-k MoE with *shard-local* capacity dispatch + expert-parallel
    all-to-all.

    Tokens are viewed as (G, T/G) where G = the data-parallel pool size, so
    routing, sort and scatter are *batched per shard with a sharded leading
    dim* — the indices never cross shards and XLA partitions every scatter /
    gather cleanly.  Cross-device movement happens exactly once in each
    direction, as the buffer resharding (G-sharded -> E-sharded): the classic
    expert-parallel all-to-all.  (A global scatter with computed indices
    forces SPMD to replicate a (T*K, d)-shaped index tensor — 51 GB/layer at
    the train_4k shape; found in §Perf iteration 1 of deepseek train_4k.)
    Capacity is per shard: C_local = ceil(T/G * K * cf / E), so drop behaviour
    is shard-local (standard for EP implementations).
    """
    from repro.distributed.act_sharding import dp_total

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    G = dp_total()
    if T % G != 0:
        G = 1
    Tl = T // G
    xt = constrain(x.reshape(G, Tl, d), "dp", None, None)

    logits = xt.astype(f32) @ p["router"]  # (G, Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, K)  # (G, Tl, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_e = expert_idx.reshape(G, Tl * K)
    # position-within-expert via per-shard stable sort (O(n log n))
    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    first = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E), side="left")
    )(sorted_e)  # (G, E)
    pos_sorted = (
        jnp.arange(Tl * K)[None, :] - jnp.take_along_axis(first, sorted_e, axis=1)
    )
    pos = jax.vmap(lambda o, ps: jnp.zeros_like(ps).at[o].set(ps))(order, pos_sorted)
    C = moe_capacity(cfg, Tl)
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)  # dropped -> overflow row

    x_rep = jnp.repeat(xt, K, axis=1)  # (G, Tl*K, d)
    buf = jnp.zeros((G, E * C + 1, d), xt.dtype)
    buf = jax.vmap(lambda b, s, xr: b.at[s].set(xr))(buf, slot, x_rep)
    # reshard G-major -> E-major: the expert-parallel all-to-all
    bufe = buf[:, : E * C].reshape(G, E, C, d).transpose(1, 0, 2, 3)
    bufe = constrain(bufe, "tp", "dp", None, None)
    h = bufe.reshape(E, G * C, d)

    a = jnp.einsum("ecd,edf->ecf", h, p["w1"])
    g = _act(cfg, a) * jnp.einsum("ecd,edf->ecf", h, p["w3"])
    y = jnp.einsum("ecf,efd->ecd", g, p["w2"])  # (E, G*C, d)
    # reshard back E-major -> G-major (second all-to-all)
    y = y.reshape(E, G, C, d).transpose(1, 0, 2, 3).reshape(G, E * C, d)
    y = constrain(y, "dp", None, None)
    y = jnp.concatenate([y, jnp.zeros((G, 1, d), y.dtype)], axis=1)

    y_tok = jnp.take_along_axis(y, slot[..., None], axis=1)  # (G, Tl*K, d)
    out = (y_tok.reshape(G, Tl, K, d) * gate_vals[..., None].astype(y.dtype)).sum(axis=2)

    if cfg.n_shared_experts:
        out = out + (_act(cfg, xt @ p["sw1"]) * (xt @ p["sw3"])) @ p["sw2"]
    return out.reshape(B, S, d)


def moe_load_balance_loss(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Switch-style aux loss — exported for the training substrate."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), cfg.n_experts, dtype=f32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
