"""A tiny cell for CPU tests: the harness's files in a scratch root, with a
model and a corpus small enough to serve in seconds."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny",
    "source": "https://huggingface.co/Qwen/Qwen3-1.7B/blob/main/config.json",
    "program_arch": "qwen3-1.7b",
    "model": {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
              "vocab_size": 512, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
              "tie_word_embeddings": True, "torch_dtype": "bfloat16"},
    "architecture": {"qk_norm": True},
    "engine": {"slots": 4, "max_len": 256, "max_gen_batch": 4},
    "retrieval": {"corpus_docs": 4096, "dim": 32, "n_topics": 64, "zipf_alpha": 1.1,
                  "doc_noise": 0.16, "query_noise": 0.32, "inter_drift": 0.42,
                  "partial_noise": 0.8, "noise_ref_dim": 64, "ivf_nlist": 32,
                  "ivf_nprobe": 8, "kmeans_sample": 4096, "kmeans_iters": 3,
                  "hot_clusters": 8, "tile_len": 512, "update_interval": 10},
    "check": {"gen_tokens": 48, "ret_stages": 8},
    "limits": {"gen_logit_gap": 0.01, "ret_dist_err": 0.0001},
}

TINY_TRAFFIC = {
    "name": "tiny-mix", "seed": 5, "loop": "open",
    "workflows": {"one-shot": 1, "multistep": 1, "irg": 1, "recomp": 1},
    "limits_s": {"one-shot": 20.0, "recomp": 30.0, "multistep": 30.0, "irg": 30.0},
    "profile": {"prompt_tokens_mean": 40, "prompt_tokens_sigma": 0.4,
                "gen_tokens_mean": 8, "gen_tokens_sigma": 0.4, "max_gen_tokens": 16,
                "iterations_mean": 1.5, "iterations_max": 2, "seed": 7},
    "rate_per_s": 3.0,
    "bursts": {"period_s": 2, "length_s": 1, "first_s": 1, "high": 1.5, "low": 0.5},
    "lead_s": 0.5, "tail_s": 30, "follow_s": 30, "trace_s": 1,
    "warm": {"scan_g_max": 4, "upload_slots_max": 4, "cache_substages": 12},
}


def make_root(tmp: Path, config=None, traffic=None) -> Path:
    """A root with BENCHMARK.json naming one tiny cell, the tiny files, and
    the repository's metric readers."""
    config = dict(config or TINY_CONFIG)
    traffic = dict(traffic or TINY_TRAFFIC)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = f"{config['name']}.{traffic['name']}"
    manifest["configs"] = [{"name": config["name"], "source": config["source"],
                            "file": f"bench/configs/{config['name']}.json",
                            "reduced": [], "why": "CPU test"}]
    manifest["workloads"] = [{"name": cell, "config": config["name"],
                              "traffic": traffic["name"], "chips": 1, "why": "CPU test"}]
    for m in manifest["per_layer"]:
        m["workloads"] = [cell]
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(REPO / "bench" / "metrics", tmp / "bench" / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp / "bench" / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (tmp / "bench" / "traffic" / f"{traffic['name']}.json").write_text(json.dumps(traffic))
    return tmp
