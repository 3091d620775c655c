"""Configuration files: the widths are the published ones the program's own
configs carry, nothing listed as reduced is a width, and the benchmark's
weights have the program's parameter layout."""
import json
from pathlib import Path

import jax
import pytest

from bench import stack, weights

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}
WIDTHS = ("hidden", "intermediate", "latent", "state", "proj", "head",
          "expansion", "experts_per_tok")


def _file(name):
    return json.loads((REPO / CONFIGS[name]["file"]).read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_widths_match_the_program_config(name):
    from repro.configs import get_config

    c = _file(name)
    prog = get_config(c["program_arch"])
    m = stack.model_dict(c)
    assert (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"]) == (prog.n_layers, prog.d_model, prog.n_heads,
                                 prog.n_kv_heads, prog.d_head, prog.d_ff, prog.vocab_size)
    assert m["qk_norm"] == prog.qk_norm
    assert m["tie_word_embeddings"] == prog.tie_embeddings
    assert float(m["rope_theta"]) == prog.rope_theta


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reduced_keys_are_listed_and_no_width(name):
    c = _file(name)
    assert sorted(CONFIGS[name]["reduced"]) == sorted(c["reduced"])
    for k in CONFIGS[name]["reduced"]:
        assert not k.endswith(("_dim", "_rank", "_size")), k
        assert not any(w in k for w in WIDTHS), k
        assert k in c["retrieval"], k


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_cell_names_a_config_and_a_mix(name):
    for cell in MANIFEST["workloads"]:
        assert cell["config"] in CONFIGS
        assert (REPO / "bench" / "traffic" / f"{cell['traffic']}.json").exists()
    assert any(cell["config"] == name for cell in MANIFEST["workloads"])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_have_the_program_layout(name):
    from repro.models import lm

    c = _file(name)
    m, cfg = stack.model_dict(c), stack.model_config(c)
    ours = jax.eval_shape(lambda k: weights._program_params(m, k), jax.random.PRNGKey(0))
    prog = jax.eval_shape(lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(prog)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_reference_layer_weights_are_the_served_ones():
    from tiny import TINY_CONFIG

    m = stack.model_dict(TINY_CONFIG)
    p = weights.program_params(m, 2**32 + 9)
    base = weights.base_key(2**32 + 9)
    for i in range(m["num_hidden_layers"]):
        w = weights.layer(m, base, i)
        assert (w["wq"] == p["segments"][0]["mixer"]["wq"][i]).all()
        assert (w["w_down"] == p["segments"][0]["ffn"]["w2"][i]).all()
    assert (weights.embedding(m, base) == p["embed"]).all()
