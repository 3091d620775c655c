"""Training substrate: optimizer, microbatching, checkpoint, data, engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import TRAIN_4K, ShapeConfig
from repro.models import lm
from repro.training.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.training.data import SyntheticTokenStream
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_step import make_train_step


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-1.7b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _batch(cfg, B=4, S=32, seed=0):
    k = jax.random.PRNGKey(seed)
    t = jax.random.randint(k, (B, S), 1, cfg.vocab_size)
    return {"tokens": t, "labels": jnp.roll(t, -1, 1)}


def test_train_step_reduces_loss(tiny):
    cfg, params = tiny
    step = jax.jit(make_train_step(cfg, OptConfig(lr=3e-3, warmup_steps=1)))
    opt = init_opt_state(params)
    batch = _batch(cfg)
    losses = []
    for _ in range(8):
        loss, params, opt, stats = step(params, opt, batch)
        losses.append(float(loss))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], losses


def test_microbatch_equivalence(tiny):
    cfg, params = tiny
    opt = init_opt_state(params)
    batch = _batch(cfg, B=4)
    l1, p1, _, _ = jax.jit(make_train_step(cfg, microbatch=0))(params, opt, batch)
    l2, p2, _, _ = jax.jit(make_train_step(cfg, microbatch=2))(params, opt, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 5e-3, f"param divergence {d}"


def test_checkpoint_roundtrip(tmp_path, tiny):
    cfg, params = tiny
    opt = init_opt_state(params)
    state = {"params": params, "opt": opt}
    save_checkpoint(str(tmp_path), 7, state, extra={"note": "x"}, keep=2)
    save_checkpoint(str(tmp_path), 14, state, keep=2)
    assert latest_step(str(tmp_path)) == 14
    step, restored, extra = restore_checkpoint(str(tmp_path), 7, like=state)
    assert step == 7 and extra == {"note": "x"}
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_prunes(tmp_path, tiny):
    cfg, params = tiny
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), s, {"p": params}, keep=2)
    from repro.training.checkpoint import latest_steps

    assert latest_steps(str(tmp_path)) == [3, 4]


def test_data_stream_deterministic_resume():
    cfg = get_config("qwen3-1.7b").reduced()
    shape = ShapeConfig("t", 64, 8, "train")
    ds = SyntheticTokenStream(cfg, shape)
    b1 = ds.batch_at(5)
    b2 = SyntheticTokenStream(cfg, shape).batch_at(5)  # fresh pipeline, same step
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].max() < cfg.vocab_size
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()


def test_elastic_runner_roundtrip(tmp_path, tiny):
    from repro.distributed.elastic import ElasticConfig, ElasticRunner
    from repro.launch.mesh import make_host_mesh

    cfg, params = tiny
    ecfg = ElasticConfig(ckpt_dir=str(tmp_path), save_every=2, keep=2)

    def build_step(mesh):
        return jax.jit(make_train_step(cfg))

    def init_fn(mesh):
        return {"params": params, "opt": init_opt_state(params)}

    runner = ElasticRunner(ecfg, make_host_mesh, build_step)
    mesh, step_fn, state, start = runner.resume_or_init(init_fn, lambda m, l: None)
    assert start == 0
    runner.maybe_save(2, state)
    mesh, step_fn, state2, start2 = runner.resume_or_init(init_fn, lambda m, l: None)
    assert start2 == 2
    # straggler detection
    assert not runner.observe_step_time(1.0, 1.0)
    for _ in range(5):
        trig = runner.observe_step_time(10.0, 1.0)
    assert trig


def test_generation_engine_continuous_batching(tiny):
    from repro.serving.engine import GenerationEngine

    cfg, params = tiny
    eng = GenerationEngine(cfg, params, max_batch=3, max_len=96, eos_id=-1)
    a = eng.add_sequence(np.arange(6) % 200 + 1, max_new=5)
    b = eng.add_sequence(np.arange(10) % 200 + 1, max_new=9)
    assert eng.batch_size == 2
    for _ in range(5):
        eng.step()
    assert eng.batch_size == 1  # a finished, slot freed
    c = eng.add_sequence(np.arange(4) % 200 + 1, max_new=3)
    while eng.batch_size:
        eng.step()
    assert len(eng.free_slots) == 3


def test_generation_engine_truncates_long_prompt(tiny):
    """Prompts longer than max_len must left-truncate (keep the suffix)
    instead of crashing on the pad-slot broadcast."""
    from repro.serving.engine import GenerationEngine

    cfg, params = tiny
    eng = GenerationEngine(cfg, params, max_batch=2, max_len=64, eos_id=-1)
    sid = eng.add_sequence(np.arange(200) % 200 + 1, max_new=2)
    # suffix kept, with decode headroom reserved (max_len - max_new)
    assert eng.seqs[sid].prompt_len == 62
    while eng.batch_size:
        eng.step()
    assert len(eng.free_slots) == 2


def test_generation_engine_sampler_not_shared(tiny):
    """Each engine must own its SamplerConfig (no shared mutable default)."""
    from repro.serving.engine import GenerationEngine

    cfg, params = tiny
    e1 = GenerationEngine(cfg, params, max_batch=1, max_len=32)
    e2 = GenerationEngine(cfg, params, max_batch=1, max_len=32)
    assert e1.sampler is not e2.sampler


def test_generation_engine_warmup_leaves_nothing_to_compile(tiny):
    """After warmup, prompts of every length and decode compile nothing,
    and the engine generates what an engine that was never warmed does."""
    from repro.serving.engine import GenerationEngine, _decode, jit_prefill

    cfg, params = tiny
    max_new = 8
    warm = GenerationEngine(cfg, params, max_batch=2, max_len=96, eos_id=-1)
    warm.warmup(max_new)
    assert warm.batch_size == 0 and len(warm.free_slots) == 2
    sizes = (jit_prefill._cache_size(), _decode._cache_size())
    for n in range(1, 100):
        warm.add_sequence(np.arange(n) % 200 + 1, max_new=max_new)
        warm.step()
        warm._clear_slots()
    assert (jit_prefill._cache_size(), _decode._cache_size()) == sizes

    def generate(eng):
        eng.add_sequence(np.arange(7) % 200 + 1, max_new=max_new)
        sid = eng.add_sequence(np.arange(40) % 200 + 1, max_new=max_new)
        seq = eng.seqs[sid]
        while eng.batch_size:
            eng.step()
        return seq.tokens

    warm.warmup(max_new)
    cold = GenerationEngine(cfg, params, max_batch=2, max_len=96, eos_id=-1)
    assert generate(warm) == generate(cold)
