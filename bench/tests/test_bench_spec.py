"""BENCHMARK.json keeps to the benchmark's contract: names, units and
characters, the keys of each entry, and a file for every name it uses."""
import json
import math
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(spec["command"]) <= 32
    assert all(_line(w) for w in spec["command"])
    assert any(w.startswith(spec["paths"][0] + "/") for w in spec["command"])
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entry_keys(spec):
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(spec["paths"][0] + "/")
        names.append(c["name"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in names
    metric_keys = {"name", "unit", "better", "source", "workloads"}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == (metric_keys - {"workloads"}) | {
            "layer", "moves"}
        assert _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    all_names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in spec[k]]
    assert len(all_names) == len(set(all_names))


def test_every_cell_reports_what_it_must(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = [w["name"] for w in spec["workloads"]]
    for cell in cells:
        own = [m["name"] for m in spec["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in own and len(own) >= 2
        layer = [m for m in spec["per_layer"]
                 if cell in m.get("workloads", cells)]
        assert layer
        for m in layer:   # each per-layer metric moves one the cell reports
            assert m["moves"] in own
    for m in spec["per_layer"]:
        assert m["moves"] in e2e


def test_every_name_has_its_file(spec):
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert set(cfg["reduced"]) <= set(cfg.get("published", {}))
        for k in cfg["reduced"]:
            assert not (k.endswith("_dim") or k.endswith("_rank")
                        or k.endswith("_size") or "head" in k)
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(ROOT, spec["paths"][0], "traffic",
                                           w["traffic"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, spec["paths"][0], "metrics",
                                           m["name"] + ".py"))


def test_run_seconds_fits_a_full_check(spec):
    runs = 2 + 14 * 24
    total = runs * (spec["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
    for m in spec["end_to_end"]:
        assert not math.isnan(m["bound"])
