"""A new configuration, traffic mix and per-layer metric are picked up from
new files and entries alone, with no edit to an existing file."""
import json

from bench import harness, manifest
from tiny import TINY_CONFIG, TINY_TRAFFIC, make_root


def test_planted_config_mix_and_metric_are_found(tmp_path):
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    cfg = dict(TINY_CONFIG, name="planted-model")
    mix = dict(TINY_TRAFFIC, name="planted-mix")
    (root / "bench" / "configs" / "planted-model.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "planted-mix.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "planted_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['seconds']\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "planted-model", "source": cfg["source"],
                           "file": "bench/configs/planted-model.json", "reduced": [],
                           "why": "planted"})
    man["workloads"].append({"name": "planted-model.planted-mix", "config": "planted-model",
                             "traffic": "planted-mix", "chips": 1, "why": "planted"})
    man["per_layer"].append({"name": "planted_metric", "unit": "s", "better": "lower",
                             "source": "host_clock", "layer": "planted", "moves": "setup_s",
                             "workloads": ["planted-model.planted-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    spec = manifest.resolve_cell(root, manifest.load_manifest(root),
                                 "planted-model.planted-mix")
    assert spec["config"]["name"] == "planted-model"
    assert spec["traffic"]["name"] == "planted-mix"
    assert [m["name"] for m in spec["per_layer"]] == ["planted_metric"]
    res = {"ctx": {"seconds": 3.0}}
    assert harness.metrics_line(root, spec, res, True) == {
        "planted_metric": {"value": 6.0, "unit": "s"}}
    # the files that were there are unchanged
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_reader_that_finds_nothing_leaves_its_metric_out(tmp_path):
    root = make_root(tmp_path)
    spec = manifest.resolve_cell(root, manifest.load_manifest(root), "tiny.tiny-mix")
    ctx = {"seconds": 3.0, "window": (0.0, 3.0), "trace": None, "trace_window": None,
           "cache0": {}, "cache1": {"hits": 0, "misses": 0}}

    class NoSpans:
        def within(self, *a):
            return []

    ctx["spans"] = NoSpans()
    assert harness.metrics_line(root, spec, {"ctx": ctx}, True) == {}
