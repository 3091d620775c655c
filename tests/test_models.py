"""Per-architecture smoke + decode/train consistency tests (reduced configs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import lm


def _batch(cfg, B=2, S=24, seed=0):
    k = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(k, (B, S), 1, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    if cfg.n_prefix_embeds:
        batch["prefix_embeds"] = jnp.full((B, cfg.n_prefix_embeds, cfg.d_model), 0.01)
    if cfg.is_encoder_decoder:
        batch["enc_embeds"] = jnp.full((B, cfg.encoder_seq, cfg.d_model), 0.01)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    """Reduced config: one forward/train step on CPU, finite loss + grads."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, S=32)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: lm.train_loss(p, cfg, batch)))(params)
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    gnorm = sum(jnp.sum(jnp.abs(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gnorm), f"{arch}: grads not finite"
    assert float(loss) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    """Teacher-forced decode logits must match the full-sequence forward —
    the core serving-correctness invariant (KV caches, ring buffers, MLA
    absorbed decode, RWKV/RG-LRU recurrences all covered)."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    B, P, extra = 2, 16, 4
    S = P + extra
    batch = _batch(cfg, B=B, S=S, seed=2)
    tokens = batch["tokens"]

    # reference: full forward logits at each position
    h, _, n_prefix = lm._forward(
        cfg, params, tokens, mode="train",
        prefix_embeds=batch.get("prefix_embeds"),
        enc_embeds=batch.get("enc_embeds"),
    )
    if n_prefix:
        h = h[:, n_prefix:, :]
    ref_logits = (h @ lm._head_weights(cfg, params)).astype(jnp.float32)

    logits, state = lm.prefill(
        params, cfg, tokens[:, :P], max_len=S + cfg.n_prefix_embeds + 4,
        prefix_embeds=batch.get("prefix_embeds"),
        enc_embeds=batch.get("enc_embeds"),
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits[:, P - 1]), rtol=2e-2, atol=2e-2,
    )
    for i in range(extra):
        logits, state = lm.decode_step(params, cfg, tokens[:, P + i], state)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits[:, P + i]),
            rtol=2e-2, atol=2e-2,
            err_msg=f"{arch}: decode step {i} diverges from forward",
        )


# case: (arch, layers in each of its first three segments, config
# overrides, prompt length per slot, whether each slot decodes)
_IN_PLACE_CASES = {
    "ragged": ("qwen3-1.7b", 3, {}, (13, 7, 4), (1, 1, 1)),
    "frozen": ("qwen3-1.7b", 3, {}, (13, 7, 4), (1, 0, 1)),
    # rglru, rglru, local_attn; window 16 and prompts of 16 and 32: every
    # decode step wraps the ring
    "ring": ("recurrentgemma-2b", 2, {}, (32, 16), (1, 1)),
    "int8": ("qwen3-1.7b", 3, {"kv_cache_dtype": "int8"}, (13, 7, 4), (1, 1, 1)),
    "unscanned": ("qwen3-1.7b", 3, {"scan_layers": False}, (13, 7, 4), (1, 1, 1)),
    "latent": ("deepseek-v2-lite-16b", 2, {}, (13, 7, 4), (1, 1, 1)),
}

# time axis of each cache leaf in the stacked (L, B, ...) decode state
_TIME_AXIS = {"k": 3, "v": 3, "k_scale": 3, "v_scale": 3, "ckv": 2, "kpe": 3}


def _cache_rows(cfg, path, n):
    """Rows of a cache leaf that hold the last positions of a sequence of
    ``n`` tokens: in the decode slab (a ring keeps position p at p % window)
    and in the cache a prefill of the ``n`` tokens leaves (the last
    ``window`` in order, left-padded)."""
    if cfg.segments[path[0].idx].mixer != "local_attn":
        return np.arange(n), np.arange(n)
    w = cfg.local_window
    pos = np.arange(max(0, n - w), n)
    return pos % w, pos - n + w


@pytest.mark.parametrize("case", list(_IN_PLACE_CASES))
def test_in_place_decode_matches_forward(case):
    """The served decode step writes each new KV row into the carried stack
    in place.  Slots of different lengths, prefilled apart and inserted as
    the engine does, are decoded teacher-forced: the logits match the
    full-sequence forward, each decoding slot's cache and state match a
    prefill of its tokens so far, and a frozen slot keeps its length and
    its cache."""
    import dataclasses

    from repro.serving.engine import _decode, _insert_impl, jit_prefill
    from repro.serving.sampler import SamplerConfig

    arch, layers, over, lens, live = _IN_PLACE_CASES[case]
    base = get_config(arch)
    segs = tuple(dataclasses.replace(s, repeat=layers) for s in base.segments[:3])
    cfg = base.reduced(segments=segs, n_layers=layers * len(segs), **over)
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    steps = 3
    B, M = len(lens), max(lens) + steps + 4
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (B, max(lens) + steps), 1, cfg.vocab_size))
    h, _, _ = lm._forward(cfg, params, jnp.asarray(tokens), mode="train")
    ref_logits = np.asarray(h @ lm._head_weights(cfg, params), np.float32)

    state = lm.init_decode_state(cfg, B, M)
    for b, n in enumerate(lens):
        _, one = jit_prefill(params, cfg, jnp.asarray(tokens[b:b + 1, :n]),
                             max_len=M)
        state = _insert_impl(state, one, b)
    before = jax.tree.map(np.asarray, state)  # _decode donates the state
    active = jnp.asarray(live, bool)
    decode_logits = jax.jit(lm.decode_step, static_argnums=1)
    for i in range(steps):
        tok = jnp.asarray([tokens[b, n + i] if live[b] else 0
                           for b, n in enumerate(lens)], jnp.int32)
        logits, _ = decode_logits(params, cfg, tok, state)
        _, state = _decode(params, state, tok, jax.random.PRNGKey(0), active,
                           cfg=cfg, sampler=SamplerConfig())
        for b, n in enumerate(lens):
            if live[b]:
                np.testing.assert_allclose(
                    np.asarray(logits[b]), ref_logits[b, n + i],
                    rtol=2e-2, atol=2e-2,
                    err_msg=f"{case}: slot {b} step {i} diverges from forward")

    np.testing.assert_array_equal(
        np.asarray(state["cache_len"]),
        [n + steps if on else n for n, on in zip(lens, live)])
    seg_leaves = lambda st: jax.tree_util.tree_flatten_with_path(
        st["segments"])[0]
    for b, n in enumerate(lens):
        if live[b]:
            # what a prefill of this slot's tokens so far leaves
            _, ref = jit_prefill(params, cfg,
                                 jnp.asarray(tokens[b:b + 1, :n + steps]),
                                 max_len=M)
            ref_b, got_n = ref, n + steps
        else:  # frozen: the cache it had, untouched
            ref_b = {"segments": jax.tree.map(lambda a: a[:, b:b + 1],
                                               before["segments"])}
            got_n = n
        for (path, got), (_, want) in zip(seg_leaves(state), seg_leaves(ref_b)):
            got, want = np.asarray(got[:, b]), np.asarray(want[:, 0])
            name = path[-1].key
            if name in _TIME_AXIS:
                slab_rows, ref_rows = _cache_rows(cfg, path, got_n)
                if not live[b]:
                    ref_rows = slab_rows
                got = np.take(got, slab_rows, axis=_TIME_AXIS[name] - 1)
                want = np.take(want, ref_rows, axis=_TIME_AXIS[name] - 1)
            if not live[b]:
                np.testing.assert_array_equal(got, want, err_msg=f"{case}: {path}")
                continue
            tol = dict(rtol=1e-3, atol=1e-3)
            if cfg.kv_cache_dtype == "int8" and name in _TIME_AXIS:
                # the decode attends quantized keys where the prefill does
                # not, so from the second layer on a row lands a few codes
                # apart; one written in the wrong place is tens of codes off
                got, want = got.astype(np.float32), want.astype(np.float32)
                tol = (dict(rtol=0, atol=4) if name in ("k", "v")
                       else dict(rtol=2e-2, atol=0))
            np.testing.assert_allclose(got, want, **tol,
                                       err_msg=f"{case}: {path}")


def test_chunked_xent_matches_dense():
    cfg = get_config("qwen3-1.7b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, S=32)
    loss_chunked = lm.train_loss(params, cfg, batch)
    import dataclasses

    cfg2 = dataclasses.replace(cfg, loss_chunk=32)
    loss_dense = lm.train_loss(params, cfg2, batch)
    np.testing.assert_allclose(float(loss_chunked), float(loss_dense), rtol=1e-5)


def test_rwkv_chunk_vs_decode_recurrence():
    """Chunked parallel WKV must equal the step recurrence exactly."""
    from repro.models import rwkv6

    cfg = get_config("rwkv6-1.6b").reduced()
    seg = cfg.segments[0]
    p = rwkv6.init_timemix(cfg, seg, jax.random.PRNGKey(3))
    B, S, d = 2, 32, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, d)) * 0.3
    out_par, _ = rwkv6.apply_timemix(cfg, seg, p, x, mode="train")
    st = rwkv6.timemix_init_state(cfg, B)
    outs = []
    for t in range(S):
        o, st = rwkv6.apply_timemix(cfg, seg, p, x[:, t : t + 1], mode="decode", state=st)
        outs.append(o)
    out_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_par), np.asarray(out_seq),
                               rtol=5e-3, atol=5e-3)


def test_int8_kv_cache_decode():
    """int8 KV cache (§Perf B2): decode logits must track the bf16 path and
    keep greedy decisions identical on the tested horizon."""
    import dataclasses

    cfg = get_config("qwen3-1.7b").reduced()
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    B, P, extra = 2, 16, 4
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, P + extra), 1,
                                cfg.vocab_size)
    lf, sf = lm.prefill(params, cfg, tokens[:, :P], max_len=P + extra + 2)
    lq, sq = lm.prefill(params, cfg8, tokens[:, :P], max_len=P + extra + 2)
    for i in range(extra):
        lf, sf = lm.decode_step(params, cfg, tokens[:, P + i], sf)
        lq, sq = lm.decode_step(params, cfg8, tokens[:, P + i], sq)
        cos = float(jnp.sum(lf * lq) / (jnp.linalg.norm(lf) * jnp.linalg.norm(lq)))
        assert cos > 0.999, f"step {i}: cosine {cos}"
        assert bool(jnp.all(jnp.argmax(lf, -1) == jnp.argmax(lq, -1)))


def test_param_count_close_to_nominal():
    """Analytic parameter counts should be in the right ballpark of the
    nominal model sizes (loose: embeddings/heads dominate small models)."""
    nominal = {
        "rwkv6-1.6b": 1.6e9, "qwen3-1.7b": 1.7e9, "phi3-mini-3.8b": 3.8e9,
        "stablelm-12b": 12e9, "qwen1.5-110b": 111e9,
        "recurrentgemma-2b": 2.7e9, "whisper-medium": 0.77e9,
        "deepseek-v2-lite-16b": 16e9, "llama4-scout-17b-a16e": 109e9,
        "paligemma-3b": 2.6e9,
    }
    for arch, n in nominal.items():
        got = get_config(arch).param_count()
        assert 0.5 * n < got < 1.9 * n, f"{arch}: {got/1e9:.2f}B vs nominal {n/1e9:.1f}B"
