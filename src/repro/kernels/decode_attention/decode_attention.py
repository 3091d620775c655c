"""Flash-decoding GQA attention Pallas TPU kernel.

Decode attention is the memory-roofline op of serving: each step streams the
whole KV cache once at arithmetic intensity ~G (query heads per KV head).
The kernel keeps the online-softmax state (m, l, acc) of every kv head of
one sequence in VMEM scratch while iterating KV tiles, so HBM traffic is
exactly one read of K and V — no score matrix, no second pass.

Layout notes (TPU):
* a block carries all KV heads of one sequence: q is (KV, G, dh) and a KV
  tile is (SB, KV, dh), so each block's last two dims are whole array dims
  whatever KV is (a single head per block would leave a 1-wide KV dim
  second-minor, which Mosaic refuses); the heads are unrolled in the kernel;
* G is padded to the 8-sublane floor in ops.py, dh is expected to be
  64/128/256 (lane-aligned);
* per-sequence valid length masks the tail tile via broadcasted_iota.

Grid: (B, S // SB) with the KV-tile index innermost.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG = -1.0e30


def _decode_attn_kernel(
    lengths_ref,  # (B,) int32 in SMEM
    q_ref,        # (KV, G, dh)
    k_ref,        # (SB, KV, dh)
    v_ref,        # (SB, KV, dh)
    o_ref,        # (KV, G, dh)
    m_ref,        # (KV, G, 1) scratch
    l_ref,        # (KV, G, 1) scratch
    acc_ref,      # (KV, G, dh) scratch
    *,
    sb: int,
    n_s_tiles: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    n_kv = q_ref.shape[0]
    for h in range(n_kv):
        q = q_ref[h].astype(f32) * scale              # (G, dh)
        k = k_ref[:, h, :].astype(f32)                # (SB, dh)
        v = v_ref[:, h, :].astype(f32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
        )                                             # (G, SB)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * sb
        s = jnp.where(col < length, s, NEG)

        m_prev = m_ref[h]                             # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (G, SB)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32
        )
        m_ref[h] = m_new

    @pl.when(j == n_s_tiles - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sb", "interpret"))
def decode_attention_pallas(
    q: jax.Array,        # (B, KV, G, dh)  — reshaped/padded by ops.py
    k_cache: jax.Array,  # (B, S, KV, dh)
    v_cache: jax.Array,  # (B, S, KV, dh)
    lengths: jax.Array,  # (B,) int32
    *,
    sb: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, KV, G, dh = q.shape
    S = k_cache.shape[1]
    sb = min(sb, S)
    assert S % sb == 0, f"cache len {S} not divisible by KV tile {sb}"
    n_s = S // sb
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(_decode_attn_kernel, sb=sb, n_s_tiles=n_s, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((None, KV, G, dh), lambda b, j, ln: (b, 0, 0, 0)),
            pl.BlockSpec((None, sb, KV, dh), lambda b, j, ln: (b, j, 0, 0)),
            pl.BlockSpec((None, sb, KV, dh), lambda b, j, ln: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, KV, G, dh), lambda b, j, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), f32),
            pltpu.VMEM((KV, G, 1), f32),
            pltpu.VMEM((KV, G, dh), f32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
    return out
