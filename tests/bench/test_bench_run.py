"""The whole harness on the CPU, on a tiny cell, with the look for a chip
skipped: the adapter runs exactly the work the scheduler charges, the
comparison passes on the served path, and it comes out false when the
timed path is broken underneath or when the lower-precision control takes
the program's place."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, manifest
from tiny import make_root

CELL = "tiny.tiny-mix"


def _run(tmp_path, seed, fault=None, with_controls=False):
    root = make_root(tmp_path)
    spec = manifest.resolve_cell(root, manifest.load_manifest(root), CELL)
    res = harness.run(root, spec, seed, 2.0, False, time.perf_counter(), fault=fault,
                      with_controls=with_controls)
    return res, harness.report(root, spec, res, False, jax.devices()[:1])


@pytest.fixture(scope="module")
def clean(tmp_path_factory):
    """A clean run, and the generation adapter it served with."""
    kept = []
    res, line = _run(tmp_path_factory.mktemp("clean"), 2**32 + 11,
                     fault=lambda st: kept.append(st.gen), with_controls=True)
    res["sequences"] = kept[0].finished_sequences()
    return res, line


def test_adapter_executes_what_is_charged(clean):
    res, line = clean
    g = res["ctx"]["gen_counts"]
    assert g["calls"] > 0 and g["mismatched_calls"] == 0
    assert g["prefill_executed"] == g["prefill_charged"] > 0
    assert g["prefill_truncated"] == 0 and g["evictions"] == 0
    assert g["tokens_executed"] == g["tokens_charged"] > 0
    assert g["tokens_outside_batch"] == 0
    assert 0 < g["steps_executed"] <= g["steps_charged"]
    spans = res["ctx"]["spans"]
    decoded = sum(i["emitted"] for *_, i in spans.within("decode", -1e18, 1e18))
    assert decoded == g["tokens_executed"]
    assert line["metrics"]["output_tokens_per_s"]["value"] > 0


def test_reference_input_is_what_the_engine_prefilled(clean):
    """The token array the engine's prefill program got ends in the prompt
    the engine kept; what precedes it is the engine's padding."""
    res, _ = clean
    seqs = res["sequences"]
    assert seqs
    for lv in seqs:
        served_in = np.asarray(lv.model_in).reshape(-1)
        assert served_in.size >= lv.seq.prompt_len == lv.prompt.size
        np.testing.assert_array_equal(served_in[served_in.size - lv.prompt.size:],
                                      lv.prompt)


def test_scan_widths_come_from_the_mix_workflows():
    from bench import stack
    from tiny import TINY_TRAFFIC

    assert stack.scan_widths(TINY_TRAFFIC, 512) == [2, 5, 8, 20]
    assert stack.scan_widths({"workflows": {"one-shot": 1}}, 512) == [5, 20]
    assert stack.scan_widths({"workflows": {"one-shot": 1}}, 8) == [5, 8]


def test_clean_run_is_correct(clean):
    res, line = clean
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"goodput_rps", "latency_p50_ms", "latency_p90_ms",
                                    "output_tokens_per_s", "setup_s"}


def test_control_fails_what_the_program_passes(clean):
    res, _ = clean
    lim = res["ctx"]["config"]["limits"]
    ctrl = res["controls"]
    served = {c["name"]: c["value"] for c in res["checks"]}
    assert served["gen_logit_gap"] <= lim["gen_logit_gap"] < ctrl["gen_logit_gap"]
    assert served["ret_dist_err"] <= lim["ret_dist_err"] < ctrl["ret_dist_err"]


def _stale_decode(st):
    """Decode steps that return the cache unchanged."""
    import repro.serving.engine as eng

    def impl(params, state, tokens, key, active, *, cfg, sampler):
        nxt, _ = eng._decode_impl(params, state, tokens, key, active, cfg=cfg,
                                  sampler=sampler)
        return nxt, state

    eng._decode = jax.jit(impl, static_argnames=("cfg", "sampler"))


def _altered_token(st):
    """Each decoded token replaced by its neighbour id as it is produced."""
    import repro.serving.engine as eng

    orig = eng._decode

    def decode(*a, **kw):
        nxt, state = orig(*a, **kw)
        return (nxt + 1) % st.cfg.vocab_size, state

    eng._decode = decode


def _altered_answer(st):
    """Each retrieval sub-stage's first answer replaced by another passage."""
    orig = st.hybrid.search_plan

    def search_plan(plan, **kw):
        out = orig(plan, **kw)
        if out.ids.size:
            out.ids[:, 0] = (out.ids[:, 0] + 1) % st.index.ids.size
        return out

    st.hybrid.search_plan = search_plan


@pytest.mark.parametrize("fault,broken", [
    (_stale_decode, "gen_logit_gap"),
    (_altered_token, "gen_logit_gap"),
    (_altered_answer, "ret_ids_mismatched"),
])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault, broken):
    import repro.serving.engine as eng

    monkeypatch.setattr(eng, "_decode", eng._decode)
    res, line = _run(tmp_path, 2**32 + 11, fault=fault)
    assert line["correct"] is False
    assert line["compared"][broken]["value"] > line["compared"][broken]["limit"]
