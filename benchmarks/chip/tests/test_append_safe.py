"""The benchmark takes what a later PR may bring without an edit to a file
that is there: on a COPY of `BENCHMARK.json` and this directory, one
configuration (an accepted one's file under a new name), one cell of it, one
per-layer metric of that cell alone (a data file over an accepted reader) and
the cell's name on every accepted list its sibling shares with another cell
are added, and the copy's own tests — these, whole — still pass. The driver
holds a PR to the END of every list; the second case puts the metric in the
MIDDLE of `per_layer`: where an entry stands is the driver's rule to keep,
and no test's. A test here that holds an entry to be "last", or a list to a
length, fails this one."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
THIS = os.path.basename(__file__)
# what is copied and grown: a configuration whose cell shares lists of
# every kind (a state gauge, an expert counter, a capture group)
FROM = "solar-open2-bf16-ep8-8l"
STEP = "kda_decode_step_roofline"  # its whole-step share: one a cell
SHARED = ("kda_state_share", "moe_read_share", "window_glue_ms")
NEW_CFG, NEW_STEP = "appended-bf16-8l", "appended_decode_step_roofline"
NEW_CELL = NEW_CFG + ".longdoc-saturated"


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(doc, path):
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def grown_copy(root, metric_at_end):
    """A checkout of the benchmark alone under `root`, with the additions."""
    chip = os.path.join(root, os.path.relpath(HERE, REPO))
    # without this file: the copy's run would copy and run again
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", THIS))
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"] if c["name"] == FROM)
    cell = next(w for w in manifest["workloads"] if w["config"] == FROM)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the lists a sibling of this configuration joins: every one that holds
    # its cell beside another's (a metric of the one cell stays that cell's)
    lists = [m for m in manifest["per_layer"]
             if cell["name"] in m.get("workloads", ())
             and len(m["workloads"]) > 1]
    assert set(SHARED) <= {m["name"] for m in lists}

    rel = os.path.dirname(entry["file"]) + "/" + NEW_CFG + ".json"
    dump({**load(os.path.join(REPO, entry["file"])), "name": NEW_CFG},
         os.path.join(root, rel))
    shutil.copy(os.path.join(chip, "metrics", STEP + ".json"),
                os.path.join(chip, "metrics", NEW_STEP + ".json"))
    manifest["configs"].append({**entry, "name": NEW_CFG, "file": rel})
    manifest["workloads"].append(
        {**cell, "name": NEW_CELL, "config": NEW_CFG})
    metric = {**by_name[STEP], "name": NEW_STEP, "workloads": [NEW_CELL]}
    names = [m["name"] for m in manifest["per_layer"]]
    at = len(names) if metric_at_end else names.index(SHARED[0])
    manifest["per_layer"].insert(at, metric)
    for m in lists:
        m["workloads"].append(NEW_CELL)
    dump(manifest, os.path.join(root, "BENCHMARK.json"))
    return chip


@pytest.mark.parametrize("metric_at_end", [True, False],
                         ids=["appended", "metric_in_the_middle"])
def test_the_tests_pass_on_a_copy_that_has_grown(tmp_path, metric_at_end):
    chip = grown_copy(str(tmp_path), metric_at_end)
    done = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(chip, "tests"), "-q",
         "-p", "no:cacheprovider"],
        cwd=str(tmp_path), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stdout + done.stderr
    assert " passed" in done.stdout and "deselected" not in done.stdout
