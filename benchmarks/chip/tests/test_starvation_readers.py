"""The readers of the starvation metrics on hand-made documents: what the
device trace sees idle against what the program's counter accounts for, and
counters that a parent commit does not serve yet."""

import json
import os

import pytest
from readers import idle_unattributed, timing_ratio_opt

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timing(**engine):
    return {"engine": engine, "loop": {}}


def ctx(gap_total_s, starved0, starved1, window_s=8.0):
    return {
        "trace": {"gap_total_s": gap_total_s, "window_s": window_s},
        "trace_span": {"timing0": timing(starved_s=starved0),
                       "timing1": timing(starved_s=starved1)},
    }


@pytest.mark.parametrize("gap_total_s, starved, want", [
    (0.80, 0.40, 5.0),  # idle inside or between programs, no host phase
    (0.52, 0.51, 0.125),  # the counter accounts for the idle
    (0.40, 0.60, -2.5),  # the counter counts time the device still ran
])
def test_idle_unattributed_is_signed(gap_total_s, starved, want):
    c = ctx(gap_total_s, 10.0, 10.0 + starved)
    assert idle_unattributed.read(c) == pytest.approx(want)


def test_idle_unattributed_needs_trace_and_counter():
    c = ctx(0.5, 1.0, 1.5)
    assert idle_unattributed.read({**c, "trace": None}) is None
    assert idle_unattributed.read({**c, "trace_span": {}}) is None
    parent = {"timing0": timing(sync_s=1.0), "timing1": timing(sync_s=2.0)}
    assert idle_unattributed.read({**c, "trace_span": parent}) is None


def window(start, end):
    return {"start": {"timing": timing(**start)},
            "end": {"timing": timing(**end)}}


@pytest.mark.parametrize("name, want", [
    ("device_starved_share", 6.0),
    ("dispatch_host_ms", 20.0),
])
def test_ratio_metrics_from_their_files(name, want):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "timing_ratio_opt"
    c = window(
        dict(starved_s=1.0, starved_n=10, clock_s=100.0, dispatch_s=2.0,
             dispatch_n=50),
        dict(starved_s=4.0, starved_n=110, clock_s=150.0, dispatch_s=6.0,
             dispatch_n=250),
    )
    assert timing_ratio_opt.read(c, **spec["args"]) == pytest.approx(want)


def test_ratio_is_left_out_on_a_parent_and_on_a_still_counter():
    parent = window(dict(dispatch_s=2.0), dict(dispatch_s=6.0))
    assert timing_ratio_opt.read(parent, "dispatch_s", "dispatch_n") is None
    still = window(dict(starved_s=1.0, starved_n=3),
                   dict(starved_s=1.0, starved_n=3))
    assert timing_ratio_opt.read(still, "starved_s", "starved_n") is None
