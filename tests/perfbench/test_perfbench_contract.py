"""BENCHMARK.json against the contract it was written to, and against the
files its names point to."""

import os
import re

import pytest

from perfbench import harness

BENCH = harness.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = [(kind, m) for kind in ("end_to_end", "per_layer")
           for m in BENCH[kind]]
CELLS = [w["name"] for w in BENCH["workloads"]]
# one case for every cell an entry lists (every cell, where it lists none)
METRIC_CELLS = [pytest.param(kind, m, cell, id=f"{m['name']}-{cell}")
                for kind, m in METRICS for cell in m.get("workloads", CELLS)]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 2 <= len(BENCH["workloads"]) <= 24 and len(BENCH["configs"]) <= 24
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 65536
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    # a full check fits the driver's budget even with all 24 cells
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["why"]) <= 200
    assert config["source"].startswith("https://") and len(config["source"]) <= 200
    assert config["file"].startswith("perfbench/configs/")
    data = harness.load_json(config["file"])
    assert data["source"] == config["source"]
    assert all(NAME.match(k) for k in config["reduced"])
    # full width and depth: the sizes of the program's own YAML of that name
    from fleetx_tpu.utils.config import get_config
    published = get_config(os.path.join(harness.ROOT, data["train_yaml"]),
                           nranks=1, overrides=["Distributed.dp_degree=1"]).Model
    for key, value in data["model"].items():
        want = published.get(key)
        if want is None and key == "ffn_hidden_size":
            want = 4 * published["hidden_size"]
        assert value == want, (key, value, want)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_files_and_metrics(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    loaded = harness.load_cell(cell["name"])
    assert loaded.chips == cell["chips"]
    harness.by_name("drivers", loaded.traffic["driver"]).run  # the driver exists
    reported = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded.per_layer
    for m in loaded.per_layer:  # reported only where the metric it moves is
        assert m["moves"] in reported, (m["name"], m["moves"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("kind,metric,cell", METRIC_CELLS)
def test_metric_entry_and_reader(kind, metric, cell):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    # the cell exists and reports this entry, and the metric it moves
    loaded = harness.load_cell(cell)
    assert metric in getattr(loaded, kind)
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        reader = harness.by_name("end_to_end", metric["name"])
    else:
        assert metric["moves"] in {m["name"] for m in loaded.end_to_end}
        assert metric["workloads"], "a per-layer entry lists its cells"
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
        reader = harness.by_name("layer_metrics", metric["name"])
    assert callable(reader.read)


def test_no_reader_is_listed_twice_for_one_end_to_end_metric():
    """``per_layer`` holds at most 128 entries, and a reader repeated under
    a tag for each cell filled them (PR 37 folded 73 into 20): cells that
    report the same end-to-end metric share ONE entry of a reader, whose
    ``workloads`` lists them."""
    seen = {}
    for m in BENCH["per_layer"]:
        key = (m["name"].split(".")[-1], m["moves"])
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]
    assert len(BENCH["per_layer"]) <= 128
    assert not [m["name"] for m in BENCH["per_layer"]
                if m["name"].startswith(("moe.", "st.", "lfm."))]


def test_names_are_unique_and_files_use_allowed_characters():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(set(names)) == len(names)
    names = [m["name"] for _, m in METRICS]
    assert len(set(names)) == len(names)
    for path in BENCH["paths"]:
        for directory, _, files in os.walk(os.path.join(harness.ROOT, path)):
            if "__pycache__" in directory:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(directory, f), harness.ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
