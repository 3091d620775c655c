"""Logical-axis sharding rules for every architecture in the zoo.

Scheme (baseline; §Perf hillclimbs depart from it per-cell):

* 2-D weight sharding: tensor-parallel over ``model``, FSDP over ``data``
  (and ``pod`` stays pure DP).  Stacked layer axes are never sharded.
* vocab-parallel embedding/head over ``model``.
* MoE expert axis over ``model`` (+ FSDP over ``data``) — expert parallelism;
  the capacity-dispatch scatter becomes XLA all-to-alls.
* KV caches: batch over data axes; heads over ``model`` when divisible,
  else head_dim (partial-sum attention), else replicated.
* ``long_500k`` (batch 1): the cache *sequence* axis shards over ``data`` —
  sequence parallelism is the only way a 500k-token cache spreads.

Everything is derived from pytree paths + shapes, so new layer types get
rules by name here, not by editing model code.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


# ---------------------------------------------------------------------------
# Layouts: how the fixed physical mesh axes map to logical roles.
#   'tp'       — data axes = (pod, data); model axis = tensor parallel;
#                weights FSDP-sharded over data (gathered per traversal)
#   'serve_tp' — like 'tp' but weights are TP-resident ONLY (replicated over
#                the data axes): no per-step weight all-gathers — the right
#                inference layout whenever W/tp fits HBM (§Perf decode cells)
#   'dp_only'  — model axis joins the data axes (pure FSDP/DP; right choice
#                for small archs where TP all-reduces dominate — see §Perf)
# ---------------------------------------------------------------------------


def dp_axes(mesh: Mesh, layout: str = "tp"):
    names = ("pod", "data", "model") if layout == "dp_only" else ("pod", "data")
    axes = tuple(a for a in names if a in mesh.axis_names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def tp_axis(mesh: Mesh, layout: str = "tp"):
    if layout in ("tp", "serve_tp") and "model" in mesh.axis_names:
        return "model"
    return None


def dp_size(mesh: Mesh, layout: str = "tp") -> int:
    n = _axis_size(mesh, "pod") * _axis_size(mesh, "data")
    if layout == "dp_only":
        n *= _axis_size(mesh, "model")
    return n


def _div(n: int, mesh: Mesh, axis) -> bool:
    if axis is None:
        return False
    if isinstance(axis, tuple):
        size = 1
        for a in axis:
            size *= _axis_size(mesh, a)
        return n % size == 0
    return n % _axis_size(mesh, axis) == 0


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

_LAST2_RULES: dict[str, tuple[Optional[str], Optional[str]]] = {
    # name -> (spec for dim -2, spec for dim -1); leading dims unsharded
    # (stacked layer axes) unless MoE handles them explicitly.
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wo": ("model", "data"),
    "wq_a": ("data", None),
    "wq_b": (None, "model"),
    "wkv_a": ("data", None),
    "wk_b": (None, "model"),
    "wv_b": (None, "model"),
    "w1": ("data", "model"),
    "w3": ("data", "model"),
    "w2": ("model", "data"),
    "sw1": ("data", "model"),
    "sw3": ("data", "model"),
    "sw2": ("model", "data"),
    "wg": ("data", "model"),
    "wr": ("data", "model"),
    "wd_w1": (None, None),
    "wd_w2": (None, None),
    "tm_w1": (None, None),
    "tm_w2": (None, None),
    "w_in1": ("data", "model"),
    "w_in2": ("data", "model"),
    "w_out": ("model", "data"),
    "w_a": ("data", "model"),
    "w_x": ("data", "model"),
    "router": (None, None),
}

_VEC_MODEL = {"bq", "bk", "bv", "lam", "b_a", "b_x", "conv_b"}


def _path_names(path) -> list[str]:
    out = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            out.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return out


def param_spec(cfg: ModelConfig, mesh: Mesh, path, leaf, layout: str = "tp") -> P:
    names = _path_names(path)
    name = names[-1]
    shape = leaf.shape
    nd = len(shape)
    # rule tokens -> physical axes under this layout
    if layout == "dp_only":
        fsdp = ("data", "model")
    elif layout == "serve_tp":
        fsdp = None  # weights TP-resident, replicated over data axes
    else:
        fsdp = "data"
    tp = tp_axis(mesh, layout)

    def ax(token, dim):
        a = {"data": fsdp, "model": tp}.get(token, token)
        return a if (a and _div(dim, mesh, a)) else None

    if name == "embed":  # (V, d): vocab-parallel + FSDP on d
        v_ax = ax("model", shape[0]) or ax("data", shape[0])
        d_ax = ax("data", shape[1]) if v_ax != fsdp else None
        return P(v_ax, d_ax)
    if name == "lm_head":  # (d, V)
        v_ax = ax("model", shape[1]) or ax("data", shape[1])
        d_ax = ax("data", shape[0]) if v_ax != fsdp else None
        return P(d_ax, v_ax)
    if name == "u":  # rwkv bonus (L, H, N)
        return P(*([None] * (nd - 2)), ax("model", shape[-2]), None)

    is_moe = "ffn" in names and name in ("w1", "w2", "w3") and nd >= 3 and (
        cfg.n_experts and shape[-3] == cfg.n_experts
    )
    if is_moe:
        # (..., E, d, ff) or (..., E, ff, d): expert-parallel over model,
        # FSDP over data on the d dim
        a, b = _LAST2_RULES[name]
        lead = [None] * (nd - 3)
        spec2 = [
            ax(a, shape[-2]) if a == "data" else None,
            ax(b, shape[-1]) if b == "data" else None,
        ]
        e_ax = ax("model", cfg.n_experts) or (
            ax("data", cfg.n_experts) if layout != "tp" else None
        )
        if e_ax == fsdp:  # expert dim took the fsdp axes; drop from dims
            spec2 = [None, None]
        return P(*lead, e_ax, *spec2)

    if name in _LAST2_RULES and nd >= 2:
        a, b = _LAST2_RULES[name]
        lead = [None] * (nd - 2)
        return P(*lead, ax(a, shape[-2]), ax(b, shape[-1]))
    if name in _VEC_MODEL and nd >= 1:
        lead = [None] * (nd - 1)
        return P(*lead, ax("model", shape[-1]))
    # norms, small loras, scalars: replicated
    return P(*([None] * nd))


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_shape,
                    layout: str = "tp") -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_spec(cfg, mesh, path, leaf, layout)),
        params_shape,
    )


# ---------------------------------------------------------------------------
# Batch / decode-state rules
# ---------------------------------------------------------------------------


def batch_spec(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
               layout: str = "tp") -> dict:
    dp = dp_axes(mesh, layout)
    sharded_b = shape.global_batch % dp_size(mesh, layout) == 0
    bax = dp if sharded_b else None
    out = {
        "tokens": P(bax, None),
        "labels": P(bax, None),
    }
    if cfg.n_prefix_embeds:
        out["prefix_embeds"] = P(bax, None, None)
    if cfg.is_encoder_decoder:
        out["enc_embeds"] = P(bax, None, None)
    return out


def decode_state_spec(cfg: ModelConfig, mesh: Mesh, batch: int, path, leaf,
                      layout: str = "tp") -> P:
    """Sharding for one leaf of the DecodeState pytree (leading dim = stacked
    layers within a segment for everything except cache_len).

    Caches shard: batch -> dp axes; *sequence* -> model axis (distributed
    softmax: XLA turns the masked softmax over a sharded S into local work +
    tiny reduction all-reduces — the sequence-sharded flash-decoding layout).
    Batch-1 long-context additionally shards S over the data axes.
    """
    names = _path_names(path)
    name = names[-1]
    dp = dp_axes(mesh, layout)
    tp = tp_axis(mesh, layout)
    sharded_b = batch % dp_size(mesh, layout) == 0
    bax = dp if sharded_b else None
    nd = len(leaf.shape)

    if name == "cache_len":
        return P(bax)

    def seq_axes(S: int):
        axes = []
        if tp and S % _axis_size(mesh, tp) == 0 and S > 1:
            axes.append(tp)
        if not sharded_b and nd >= 3 and S > 1:
            size = dp_size(mesh, layout)
            if (S // (int(np.prod([_axis_size(mesh, a) for a in axes])) or 1)) % size == 0:
                axes = (list(dp) if isinstance(dp, tuple) else [dp]) + axes
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def mod_ax(dim: int):
        return tp if (tp and dim % _axis_size(mesh, tp) == 0) else None

    if name in ("k", "v") and "enc_kv" in names:  # (L, B, Se, KV, dh)
        sax = seq_axes(leaf.shape[2])
        if sax is None and tp and leaf.shape[3] % _axis_size(mesh, tp) == 0:
            # sequence not shardable (e.g. enc-dec cross KV, 1500 frames):
            # shard heads instead so per-step reshards disappear
            return P(None, bax, None, tp, None)
        return P(None, bax, sax, None, None)
    if name in ("k", "v"):  # heads-major self-attention cache (L, B, KV, S, dh)
        sax = seq_axes(leaf.shape[3])
        if sax is None and tp and leaf.shape[2] % _axis_size(mesh, tp) == 0:
            return P(None, bax, tp, None, None)
        return P(None, bax, None, sax, None)
    if name in ("k_scale", "v_scale"):  # (L, B, KV, S) int8-cache scales
        return P(None, bax, None, seq_axes(leaf.shape[3]))
    if name == "ckv":  # (L, B, S, r)
        return P(None, bax, seq_axes(leaf.shape[2]), None)
    if name == "kpe":  # (L, B, rope_dim, S)
        return P(None, bax, None, seq_axes(leaf.shape[3]))
    if name == "S":  # rwkv state (L, B, H, N, N)
        return P(None, bax, mod_ax(leaf.shape[2]), None, None)
    if name == "x_prev":  # (L, B, 1, d)
        return P(None, bax, None, mod_ax(leaf.shape[-1]))
    if name == "h":  # rglru (L, B, W)
        return P(None, bax, mod_ax(leaf.shape[-1]))
    if name == "conv":  # (L, B, cw-1, W)
        return P(None, bax, None, mod_ax(leaf.shape[-1]))
    if name == "ffn":  # rwkv cmix token shift (L, B, 1, d)
        return P(None, bax, None, mod_ax(leaf.shape[-1]))
    # enc_kv k/v handled by ("k","v") above; default: batch only
    spec = [None] * nd
    if nd >= 2:
        spec[1] = bax
    return P(*spec)


def decode_state_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, state_shape,
                           layout: str = "tp"):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, decode_state_spec(cfg, mesh, batch, path, leaf, layout)
        ),
        state_shape,
    )


def to_named(mesh: Mesh, tree_of_specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
