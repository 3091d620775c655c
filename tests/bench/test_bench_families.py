"""Model families: a family added as a file under ``bench/families`` is
found through a configuration's ``family`` and used to build, check and
count the model, with no other harness file touched; a missing or unknown
family is an error naming the known ones; and the dense family gives
exactly what the modules it names give."""
import time

import jax
import numpy as np
import pytest

from bench import flops, harness, manifest, stack, weights
from bench.reference import lm
from tiny import TINY_CONFIG, make_root

# the dense family, each of its functions counting its calls
COUNTING = '''
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location("counted_dense",
                                               Path(__file__).with_name("dense.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)
CALLS = {}


def _counted(name):
    def call(*a, **kw):
        CALLS[name] = CALLS.get(name, 0) + 1
        return getattr(_dense, name)(*a, **kw)
    return call


program_config = _counted("program_config")
program_params = _counted("program_params")
score = _counted("score")
prefill_flops = _counted("prefill_flops")
decode_flops = _counted("decode_flops")
'''


def test_a_family_added_as_a_file_is_built_checked_and_counted(tmp_path):
    root = make_root(tmp_path, config=dict(TINY_CONFIG, family="counting"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    (root / "bench" / "families" / "counting.py").write_text(COUNTING)
    spec = manifest.resolve_cell(root, manifest.load_manifest(root), "tiny.tiny-mix")
    fam = spec["family"]
    res = harness.run(root, spec, 2**32 + 21, 2.0, False, time.perf_counter(),
                      with_controls=True)
    assert fam.CALLS == {"program_config": 1, "program_params": 1, "score": 3}
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert res["controls"]["gen_logit_gap"] > TINY_CONFIG["limits"]["gen_logit_gap"]

    # gen_mfu counts the window's prefills and decode steps through the family
    ctx = dict(res["ctx"], trace={"module_s": {"jit_prefill": 0.5, "jit__decode_impl": 1.5}},
               trace_window=res["ctx"]["window"], device_kind="TPU v5 lite")
    read = manifest.load_reader(root, "gen_mfu")
    got = read(ctx)
    assert got > 0 and fam.CALLS["prefill_flops"] > 0 and fam.CALLS["decode_flops"] > 0
    dense = manifest.load_family(root, TINY_CONFIG)
    assert got == read(dict(ctx, family=dense))
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("family", ["no-such-family", None])
def test_a_missing_or_unknown_family_names_the_known_ones(tmp_path, family):
    cfg = {k: v for k, v in TINY_CONFIG.items() if k != "family"}
    if family is not None:
        cfg["family"] = family
    root = make_root(tmp_path, config=cfg)
    with pytest.raises(SystemExit, match=r"known families: \['dense'\]") as e:
        manifest.resolve_cell(root, manifest.load_manifest(root), "tiny.tiny-mix")
    said = f"model family {family!r}" if family else "no model family"
    assert said in str(e.value)


def test_dense_gives_what_the_modules_it_names_give(tmp_path):
    dense = manifest.load_family(make_root(tmp_path), TINY_CONFIG)
    m, seed = stack.model_dict(TINY_CONFIG), 2**32 + 17
    assert dense.program_config(TINY_CONFIG) == stack.model_config(TINY_CONFIG)
    ours, theirs = dense.program_params(m, seed), weights.program_params(m, seed)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rng = np.random.default_rng(3)
    seqs = [{"tokens": rng.integers(0, m["vocab_size"], n), "positions": np.arange(n - 4, n),
             "cands": rng.integers(0, m["vocab_size"], (4, 2))} for n in (9, 30)]
    for precision in ("f32", "fp8"):
        got = dense.score(m, seed, seqs, precision=precision)
        want = lm.score(m, seed, seqs, precision=precision)
        for g, w in zip(got, want):
            assert g.keys() == w.keys()
            for k in g:
                np.testing.assert_array_equal(g[k], w[k])
    for n in (1, 37, 512):
        assert dense.prefill_flops(m, n) == flops.prefill_flops(m, n)
        assert dense.decode_flops(m, n) == flops.decode_flops(m, n)
