"""Configuration files: the widths are the published ones the program's own
configs carry, nothing listed as reduced is a width, every file names a model
family that loads, and the family's weights have the program's parameter
layout.  Nothing here knows a family's keys: the program's configuration
comes from the family."""
import json
from pathlib import Path

import jax
import pytest

from bench import manifest, stack, weights

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}
# every configuration file, those no cell uses yet too, by name
FILES = {p.stem: p for p in (REPO / "bench" / "configs").glob("*.json")}
WIDTHS = ("hidden", "intermediate", "latent", "state", "proj", "head",
          "expansion", "experts_per_tok")
# ModelConfig fields a configuration takes from its source unchanged: widths
# and the architecture's facts
PUBLISHED = ("d_model", "n_heads", "n_kv_heads", "d_head", "d_ff", "vocab_size",
             "kv_lora_rank", "q_lora_rank", "rope_head_dim", "nope_head_dim",
             "v_head_dim", "n_shared_experts", "moe_top_k", "moe_d_ff", "lru_width",
             "rwkv_head_size", "qk_norm", "tie_embeddings", "rope_theta")
# fields of scale a cut may reduce, with the configuration file's key for each
SCALE = (("n_layers", "num_hidden_layers"), ("n_experts", "n_routed_experts"))


def _file(name):
    return json.loads(FILES[name].read_text())


def _entry_file(name):
    return json.loads((REPO / CONFIGS[name]["file"]).read_text())


@pytest.mark.parametrize("name", sorted(FILES))
def test_every_config_names_a_family_that_loads(name):
    fam = manifest.load_family(REPO, _file(name))
    for f in ("program_config", "program_params", "score", "prefill_flops", "decode_flops"):
        assert callable(getattr(fam, f)), f


@pytest.mark.parametrize("name", sorted(FILES))
def test_widths_match_the_program_config(name):
    from repro.configs import get_config

    c = _file(name)
    ours = manifest.load_family(REPO, c).program_config(c)
    prog = get_config(c["program_arch"])
    for f in PUBLISHED:
        assert getattr(ours, f) == getattr(prog, f), f
    for f, key in SCALE:
        if key not in c["reduced"]:
            assert getattr(ours, f) == getattr(prog, f), f
    assert ([(s.mixer, s.ffn) for s in ours.segments]
            == [(s.mixer, s.ffn) for s in prog.segments])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reduced_keys_are_listed_and_no_width(name):
    c = _entry_file(name)
    assert sorted(CONFIGS[name]["reduced"]) == sorted(c["reduced"])
    for k in CONFIGS[name]["reduced"]:
        assert not k.endswith(("_dim", "_rank", "_size")), k
        assert not any(w in k for w in WIDTHS), k
        assert k in c["model"] or k in c["retrieval"], k


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_cell_names_a_config_and_a_mix(name):
    for cell in MANIFEST["workloads"]:
        assert cell["config"] in CONFIGS
        assert (REPO / "bench" / "traffic" / f"{cell['traffic']}.json").exists()
    assert any(cell["config"] == name for cell in MANIFEST["workloads"])


@pytest.mark.parametrize("name", sorted(FILES))
def test_weights_have_the_program_layout(name):
    from repro.models import lm

    c = _file(name)
    fam = manifest.load_family(REPO, c)
    m, cfg = stack.model_dict(c), fam.program_config(c)
    ours = jax.eval_shape(lambda: fam.program_params(m, 2**32 + 3))
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
