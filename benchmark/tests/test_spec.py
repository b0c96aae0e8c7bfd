"""The benchmark file against its contract, and the harness driven by data:
a configuration, a mix, a generator kind, a cell and a per-layer metric that
only ``tests/rehearsal`` adds are found by their names."""

import json
import os
import re

import pytest

from benchmark.spec import Spec, SpecError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "BENCHMARK.json")
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal",
                         "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|expansion|experts_per_tok)")


@pytest.fixture(scope="module")
def bench():
    with open(BENCH) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(BENCH) <= 64 * 1024
    assert bench["paths"] == ["benchmark"]
    assert bench["command"][:2] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # A full check with all 24 cells has to fit into 43200 seconds.
    cells = 24
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200 <= 43200


def test_configs_name_their_files_and_cut_no_width(bench):
    assert 1 <= len(bench["configs"]) <= 24
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"] and held["name"] == c["name"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert held["published"][key] != held[key]
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_cells_are_unique_one_chip_and_say_why(bench):
    assert 1 <= len(bench["workloads"]) <= 24
    names = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_metrics_follow_the_contract(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert 1 <= len(e2e) <= 16 and 1 <= len(bench["per_layer"]) <= 128
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(bench):
    spec = Spec(BENCH)
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        sat = w["name"].endswith("_sat")
        assert ("out_tok_s" in e2e) == sat
        assert ("ttft_per_256tok_p50_ms" in e2e) == (not sat)
        for m in cell.per_layer:        # each has a reader of its own
            assert hasattr(spec.load_module(
                "layer_metrics", m["name"] + ".py"), "read")
        assert hasattr(spec.load_module(
            "generators", cell.traffic["kind"] + ".py"), "Generator")
        if cell.traffic["kind"] == "paced":
            assert isinstance(cell.traffic["rate_per_s"], float)
            assert "gen_late_p99_ms" in [m["name"] for m in cell.per_layer]


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_a_dummy_of_each_kind_is_found_by_files_and_entries_alone():
    spec = Spec(REHEARSAL)
    cell = spec.cell("tiny_trickle")
    assert cell.config["name"] == "tiny"                 # a configuration
    assert cell.traffic["kind"] == "trickle"             # a mix
    gen = spec.load_module("generators", "trickle.py")   # a generator kind
    assert gen.__file__.endswith(
        os.path.join("rehearsal", "generators", "trickle.py"))
    names = [m["name"] for m in cell.per_layer]          # a per-layer metric
    assert "released_count" in names and "occupancy_pct" not in names
    reader = spec.load_module("layer_metrics", "released_count.py")
    assert reader.__file__.startswith(os.path.dirname(REHEARSAL))
    # What the rehearsal does not add comes from the benchmark's own files.
    shared = spec.load_module("layer_metrics", "decode_tick_p50_ms.py")
    assert shared.__file__.startswith(os.path.join(ROOT, "benchmark",
                                                   "layer_metrics"))
    # None of these names appears in the harness's own code.
    for mod in ("harness.py", "run.py", "driver.py", "spec.py"):
        with open(os.path.join(ROOT, "benchmark", mod)) as f:
            text = f.read()
        for word in ("trickle", "released_count", "yi6b", "mistral",
                     "chat_", "reasoning"):
            assert word not in text, (mod, word)


def test_unknown_names_are_errors():
    spec = Spec(BENCH)
    with pytest.raises(SpecError):
        spec.cell("no_such_cell")
    with pytest.raises(SpecError):
        spec.find("traffic", "no_such_mix.json")
    with pytest.raises(SpecError):
        Spec(os.path.join(ROOT, "no_such_file.json"))
