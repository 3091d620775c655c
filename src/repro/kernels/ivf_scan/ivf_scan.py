"""Fused IVF distance + top-k Pallas TPU kernel.

The paper's retrieval hot loop computes, per (query, cluster) work item, the
L2 distances of the query to every vector in the cluster and keeps the top-k.
A GPU library does this as a distance GEMM followed by a separate selection
pass through global memory.  The TPU-native formulation fuses both:

* the distance matrix tile (QB x LB) is produced on the MXU from a
  ``q @ tile^T`` matmul plus norm terms and *never leaves VMEM*;
* a k-pass min/mask selection reduces the tile into a running (QB, k)
  scoreboard held in VMEM scratch across the cluster's row tiles;
* the cluster id -> slab row indirection is a *scalar-prefetch* BlockSpec
  index_map (the same mechanism paged-attention kernels use), so gathering
  the right cluster tile costs no extra HBM copy.

Grid: (n_groups, L // LB), j (row-tile) innermost so scratch carries the
scoreboard across row tiles of one group.

Output per group: (QB, k) distances + row indices — k values per query
instead of an (Q, N) distance dump, which is what makes the hot-cache path
bandwidth-cheap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
BIG = 3.0e38  # plain python float: jnp constants may not be closure-captured
BIG_INT = 2**30


def _kpass_select(d2: jax.Array, pos: jax.Array, payload: jax.Array, k: int):
    """Top-k smallest of d2 (QB, M), ties to the lowest ``pos``.

    Returns (vals (QB, k), payload at the selected positions (QB, k)).  The
    payload is picked with a masked min rather than a gather: Mosaic has no
    lowering for an in-kernel ``take_along_axis``.
    """
    vals, outs = [], []
    work = d2
    for _ in range(k):
        m = jnp.min(work, axis=1, keepdims=True)  # (QB, 1)
        cand = jnp.where(work <= m, pos, BIG_INT)
        sel = jnp.min(cand, axis=1, keepdims=True)  # first argmin
        hit = pos == sel
        vals.append(m)
        outs.append(jnp.min(jnp.where(hit, payload, BIG_INT), axis=1,
                            keepdims=True))
        work = jnp.where(hit, BIG, work)
    return jnp.concatenate(vals, axis=1), jnp.concatenate(outs, axis=1)


def _ivf_scan_kernel(
    # scalar prefetch
    group_cluster,  # (G,) int32
    # inputs
    q_ref,          # (QB, d)
    slab_ref,       # (LB, d)
    valid_ref,      # (C,) int32 (full, in SMEM)
    # outputs
    dist_ref,       # (QB, k)
    idx_ref,        # (QB, k)
    # scratch
    best_d,         # (QB, k) f32
    best_i,         # (QB, k) i32
    *,
    k: int,
    lb: int,
    n_l_tiles: int,
):
    g = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, BIG)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...].astype(f32)          # (QB, d)
    tile = slab_ref[...].astype(f32)    # (LB, d)
    # squared L2 via MXU matmul + norms
    qn = jnp.sum(q * q, axis=1, keepdims=True)          # (QB, 1)
    tn = jnp.sum(tile * tile, axis=1)[None, :]          # (1, LB)
    # HIGHEST: the MXU's default f32 pass rounds operands to bf16, which
    # reorders near-tied candidates against an exact (float64) scan
    d2 = qn - 2.0 * jax.lax.dot_general(
        q, tile, (((1,), (1,)), ((), ())), preferred_element_type=f32,
        precision=lax.Precision.HIGHEST,
    ) + tn                                              # (QB, LB)

    nvalid = valid_ref[group_cluster[g]]
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1) + j * lb
    d2 = jnp.where(col < nvalid, d2, BIG)

    bv, bi = _kpass_select(d2, col, col, k)             # block top-k
    # merge with running scoreboard: k-pass over the 2k candidates
    cat_d = jnp.concatenate([best_d[...], bv], axis=1)  # (QB, 2k)
    cat_i = jnp.concatenate([best_i[...], bi], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, cat_d.shape, 1)
    md, mi = _kpass_select(cat_d, pos, cat_i, k)
    best_d[...] = md
    best_i[...] = mi

    @pl.when(j == n_l_tiles - 1)
    def _fin():
        out_d = best_d[...]
        dist_ref[...] = jnp.where(out_d >= BIG, jnp.inf, out_d)
        idx_ref[...] = best_i[...]


@functools.partial(jax.jit, static_argnames=("k", "lb", "interpret"))
def ivf_scan_pallas(
    q_groups: jax.Array,       # (G, QB, d)
    group_cluster: jax.Array,  # (G,) int32
    slab: jax.Array,           # (C, L, d)
    valid: jax.Array,          # (C,) int32
    k: int,
    *,
    lb: int = 512,
    interpret: bool = False,
):
    G, QB, d = q_groups.shape
    C, L, _ = slab.shape
    lb = min(lb, L)
    assert L % lb == 0, f"slab tile {L} not divisible by block {lb}"
    n_l = L // lb

    kernel = functools.partial(_ivf_scan_kernel, k=k, lb=lb, n_l_tiles=n_l)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, n_l),
        in_specs=[
            pl.BlockSpec((None, QB, d), lambda g, j, gc: (g, 0, 0)),
            pl.BlockSpec((None, lb, d), lambda g, j, gc: (gc[g], j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((None, QB, k), lambda g, j, gc: (g, 0, 0)),
            pl.BlockSpec((None, QB, k), lambda g, j, gc: (g, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((QB, k), f32),
            pltpu.VMEM((QB, k), jnp.int32),
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((G, QB, k), f32),
        jax.ShapeDtypeStruct((G, QB, k), jnp.int32),
    ]
    dists, idx = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(group_cluster, q_groups, slab, valid.astype(jnp.int32))
    return dists, idx
