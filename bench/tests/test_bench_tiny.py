"""A configuration and its cell added as files alone: a new tiny config file
beside the others, and a new cell in the spec listed by every metric, make a
tiny cell that the CPU tests can run, with no other file edited."""
import json
import shutil

import bench_tiny

from bench import harness

CELL = "extra-3b.l3.steady"


def _spec_with_a_new_cell() -> dict:
    spec = harness.load_spec()
    spec["configs"].append({
        "name": "extra-3b.l3", "source": "https://example.org/extra-3b",
        "file": "bench/configs/extra-3b.l3.json", "reduced": [],
        "why": "a period of two Mamba layers and one attention layer"})
    spec["workloads"].append({"name": CELL, "config": "extra-3b.l3",
                              "traffic": "steady", "chips": 1,
                              "why": "the new configuration's cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    return spec


def test_a_new_config_file_and_cell_make_a_tiny_cell(tmp_path):
    configs = tmp_path / "configs"
    shutil.copytree(bench_tiny.CONFIGS, configs)
    cfg = json.loads((configs / "tiny-hybrid.json").read_text())
    cfg.update(name="tiny-extra", num_hidden_layers=3, period=[
        {"mixer": "mamba", "ffn": "mlp"}, {"mixer": "mamba", "ffn": "mlp"},
        {"mixer": "attention", "ffn": "mlp"}])
    (configs / "tiny-extra.json").write_text(json.dumps(cfg))

    spec = bench_tiny.tiny_spec(_spec_with_a_new_cell(), configs)
    cells = [w["name"] for w in spec["workloads"]]
    assert cells == [f"{n}.steady" for n in bench_tiny.config_names(configs)]
    assert "tiny-extra.steady" in cells and CELL not in cells
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert m.get("workloads", cells) == cells

    r = bench_tiny.run("tiny-extra.steady", spec=spec)
    assert r["correct"], r["checks"]
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert {"gnorm_gap", "grad_leaf_gap", "change1_leaf_gap"} <= set(r["checks"])


def test_the_tiny_spec_has_a_cell_for_each_tiny_config():
    spec = bench_tiny.tiny_spec()
    names = bench_tiny.config_names()
    assert {"tiny-hybrid", "tiny-lm", "tiny-mamba"} <= set(names)
    assert [c["name"] for c in spec["configs"]] == names
    assert [w["config"] for w in spec["workloads"]] == names
    for c in spec["configs"]:
        assert harness.load_config(spec, c["name"])["name"] == c["name"]
