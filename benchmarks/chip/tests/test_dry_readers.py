"""The readers of the dry-gap metrics on hand-made documents: the idle that
neither of the program's accounts explains, the host-side share of an empty
device before the profiler attaches, and the three ratios that are data files
for `timing_ratio_opt`. A parent commit serves none of the `dry_*` counters
nor `turn_s`: every one of the five is then left out, none raises."""

import json
import os

import pytest
from readers import idle_unseen, timing_ratio_opt, timing_ratio_pre_trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("device_dry_share", "dry_dispatch_share", "host_turn_ms",
       "idle_unseen_share", "device_empty_share_untraced")


def timing(**engine):
    return {"engine": engine, "loop": {}}


def spec_of(name):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def traced(gap_total_s, t0, t1, window_s=8.0, start=None):
    return {
        "trace": {"gap_total_s": gap_total_s, "window_s": window_s},
        "trace_span": {"timing0": timing(**t0), "timing1": timing(**t1)},
        "start": {"timing": timing(**(start or {}))},
    }


@pytest.mark.parametrize("gap_total_s, starved, dry, want", [
    (0.70, 0.0, 0.66, 0.5),  # the dry gaps explain the idle
    (0.70, 0.30, 0.32, 1.0),  # both accounts together
    (0.70, 0.0, 0.0, 8.75),  # neither sees it: idle inside programs
    (0.40, 0.10, 0.50, -2.5),  # an account counts time the device ran
])
def test_idle_unseen_is_signed(gap_total_s, starved, dry, want):
    c = traced(gap_total_s, dict(starved_s=5.0, dry_s=2.0),
               dict(starved_s=5.0 + starved, dry_s=2.0 + dry))
    assert idle_unseen.read(c) == pytest.approx(want)
    assert spec_of("idle_unseen_share") == {"reader": "idle_unseen"}


def test_idle_unseen_needs_trace_and_both_counters():
    c = traced(0.5, dict(starved_s=1.0, dry_s=0.0),
               dict(starved_s=1.5, dry_s=0.2))
    assert idle_unseen.read(c) is not None
    assert idle_unseen.read({**c, "trace": None}) is None
    assert idle_unseen.read({**c, "trace_span": {}}) is None
    parent = {"timing0": timing(starved_s=1.0),
              "timing1": timing(starved_s=1.5)}
    assert idle_unseen.read({**c, "trace_span": parent}) is None


def test_empty_share_untraced_reads_window_open_to_capture_start():
    spec = spec_of("device_empty_share_untraced")
    assert spec["reader"] == "timing_ratio_pre_trace"
    c = traced(0.7,
               dict(starved_s=1.1, dry_s=2.5, clock_s=120.0),
               # the traced slice's own increase is not this metric's
               dict(starved_s=1.1, dry_s=3.2, clock_s=128.0),
               start=dict(starved_s=1.0, dry_s=2.0, clock_s=100.0))
    got = timing_ratio_pre_trace.read(c, **spec["args"])
    assert got == pytest.approx(100.0 * (0.1 + 0.5) / 20.0)
    # no gap at all is a reading of 0, not a missing one
    still = traced(0.0, dict(starved_s=1.0, dry_s=2.0, clock_s=120.0), {},
                   start=dict(starved_s=1.0, dry_s=2.0, clock_s=100.0))
    assert timing_ratio_pre_trace.read(still, **spec["args"]) == 0.0


def test_empty_share_untraced_is_left_out_without_slice_or_counter():
    args = spec_of("device_empty_share_untraced")["args"]
    full = dict(starved_s=1.0, dry_s=2.0, clock_s=100.0)
    later = dict(starved_s=1.5, dry_s=2.5, clock_s=120.0)
    c = traced(0.7, later, later, start=full)
    assert timing_ratio_pre_trace.read(c, **args) is not None
    assert timing_ratio_pre_trace.read({**c, "trace_span": {}}, **args) is None
    for lacking in (
        traced(0.7, later, later, start=dict(starved_s=1.0, clock_s=100.0)),
        traced(0.7, dict(starved_s=1.5, clock_s=120.0), later, start=full),
    ):
        assert timing_ratio_pre_trace.read(lacking, **args) is None
    frozen = traced(0.7, full, later, start=full)  # the clock stood still
    assert timing_ratio_pre_trace.read(frozen, **args) is None


def window(start, end):
    return {"start": {"timing": timing(**start)},
            "end": {"timing": timing(**end)}}


@pytest.mark.parametrize("name, want", [
    ("device_dry_share", 1.5),
    ("dry_dispatch_share", 25.0),
    ("host_turn_ms", 30.0),
])
def test_dry_ratios_from_their_files(name, want):
    spec = spec_of(name)
    assert spec["reader"] == "timing_ratio_opt"
    c = window(
        dict(dry_s=1.0, dry_n=10, clock_s=100.0, turn_s=40.0, dispatch_n=50),
        dict(dry_s=1.75, dry_n=60, clock_s=150.0, turn_s=46.0,
             dispatch_n=250),
    )
    assert timing_ratio_opt.read(c, **spec["args"]) == pytest.approx(want)
    # no gap in the window reads 0: a share of dispatches is defined there,
    # where a mean over the gaps has nothing to divide by (the metric of
    # that kind left the manifest in PR 48)
    none = window(
        dict(dry_s=1.0, dry_n=10, clock_s=100.0, turn_s=40.0, dispatch_n=50),
        dict(dry_s=1.0, dry_n=10, clock_s=150.0, turn_s=40.0, dispatch_n=250),
    )
    assert timing_ratio_opt.read(none, **spec["args"]) == 0.0
    # a parent commit without the counter: left out
    parent = window(dict(clock_s=100.0, dispatch_n=50),
                    dict(clock_s=150.0, dispatch_n=250))
    assert timing_ratio_opt.read(parent, **spec["args"]) is None


def test_the_five_are_in_the_manifest_for_every_cell():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # looked up by name: later PRs append entries behind these
    by_name = {m["name"]: m for m in per_layer if m["name"] in NEW}
    assert set(by_name) == set(NEW)
    for m in by_name.values():
        assert "workloads" not in m and m["moves"] == "out_tok_per_s"
        assert m["better"] == "lower"
    assert by_name["idle_unseen_share"]["layer"] == "device"
    assert by_name["idle_unseen_share"]["source"] == "device_trace"
    assert by_name["dry_dispatch_share"]["source"] == "program_counter"
