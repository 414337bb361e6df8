"""The manifest and every data file load and cross-reference: a later PR
adds files and entries, and these tests say whether they hang together."""

import glob
import importlib
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load(os.path.join(REPO, "BENCHMARK.json"))


def cells_of(metric, manifest):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks/chip"]
    assert 1 <= manifest["run_seconds"] <= 51
    # 2 + 14 x 24 cells must fit the check's 43,200 s at this length
    s = manifest["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(manifest)) < 64 * 1024


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in ("device_trace", "program_span",
                                           "program_counter", "host_clock")
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    assert len(names) == len(set(names)), "duplicate names"
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1


def test_cells_reference_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["config"] in configs, w
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        spec = load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(
            os.path.join(HERE, "generators", spec["generator"] + ".py"))
        cfg = load(os.path.join(REPO, configs[w["config"]]["file"]))
        assert cfg["chips"] == w["chips"]
    assert used == set(configs), "a configuration no cell uses"
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def test_configs_state_their_cut(manifest):
    for c in manifest["configs"]:
        cfg = load(os.path.join(REPO, c["file"]))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head_dim)$", key), f"{key} is a width"
        for key in ("assumed", "deployment", "engine_flags", "rehearse"):
            assert key in cfg, (c["name"], key)


def test_every_cell_reports_enough(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in manifest["workloads"]:
        mine = [m for m in manifest["end_to_end"]
                if w["name"] in cells_of(m, manifest)]
        assert len(mine) >= 2, f"{w['name']} needs setup_s and one more"
        assert any(w["name"] in cells_of(m, manifest)
                   for m in manifest["per_layer"])


def test_per_layer_metrics_have_files_readers_and_moves(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    known_cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # the metric's file says how it is read and nothing else: which
        # cells report it, its unit and its layer are the manifest's alone,
        # so a new cell or metric never edits a file that exists
        spec = load(os.path.join(HERE, "metrics", m["name"] + ".json"))
        assert set(spec) <= {"reader", "args"}, m["name"]
        reader = importlib.import_module("readers." + spec["reader"])
        assert callable(reader.read)
        assert m["moves"] in e2e
        assert set(cells_of(m, manifest)) <= known_cells
        # the metric it should move is reported wherever this one is
        assert set(cells_of(m, manifest)) <= set(
            cells_of(e2e[m["moves"]], manifest)), m["name"]
    on_disk = {os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(HERE, "metrics", "*.json"))}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}


def test_layers_are_spelled_one_way(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert len({re.sub(r"\W+", "", x).lower() for x in layers}) == len(layers)


def test_peaks_name_their_source():
    for kind, peak in load(os.path.join(HERE, "peaks.json")).items():
        assert peak["source"] and peak["hbm_bytes_per_s"] > 0, kind


def test_file_names_use_allowed_characters():
    for root, _, files in os.walk(HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)


def test_a_new_cell_is_one_entry(manifest):
    """Adding a cell is one `workloads` entry: every metric without a
    `workloads` list of its own is the new cell's too, with no file edited."""
    import copy
    import run
    grown = copy.deepcopy(manifest)
    grown["workloads"].append({"name": "new.cell", "config": "c",
                               "traffic": "t", "chips": 1, "why": "w"})
    for m in grown["end_to_end"] + grown["per_layer"]:
        assert ("new.cell" in run.cells_of(m, grown)) == ("workloads" not in m)
    assert "new.cell" in run.cells_of(
        {m["name"]: m for m in grown["end_to_end"]}["setup_s"], grown)


def test_a_reader_that_raises_fails_the_run_and_none_is_left_out(
        manifest, monkeypatch):
    import run
    one = {"workloads": [{"name": "a"}],
           "per_layer": [{"name": "m1", "unit": "ms"},
                         {"name": "m2", "unit": "ms"},
                         {"name": "m3", "unit": "ms", "workloads": ["b"]}]}
    specs = {"m1": {"reader": "client_value", "args": {"name": "x"}},
             "m2": {"reader": "client_value", "args": {"name": "gone"}}}
    monkeypatch.setattr(
        run, "load_json", lambda p: specs[os.path.basename(p)[:-5]])
    failures = []
    out = run.read_per_layer(one, {"name": "a"}, {"values": {"x": 1.5}},
                             failures)
    assert out == {"m1": {"value": 1.5, "unit": "ms"}} and not failures
    out = run.read_per_layer(one, {"name": "a"}, {}, failures)  # KeyError
    assert out == {} and len(failures) == 3  # two readers raised, none read
