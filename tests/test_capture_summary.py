"""engine/capture_summary.py: a capture reduced to the chip's seconds by
program and by named scope, the one registry of scopes behind it
(metrics_contract.DEVICE_SCOPES), and the debug surface that serves the
summary (POST /debug/profile/stop, GET /debug/profile/summary,
`device.last_capture` of GET /debug/programs)."""

from __future__ import annotations

import ast
import asyncio
import functools
import glob
import importlib.util
import json
import os
import subprocess
import sys

import pytest
from aiohttp.test_utils import TestClient, TestServer

from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine import capture_summary as cs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "vllm_production_stack_tpu")
BENCH = os.path.join(ROOT, "benchmarks", "chip")
MS = 1e9  # picoseconds in a millisecond: the fixtures below are laid in ms


@functools.cache
def _trace_reduce():
    """benchmarks/chip/trace_reduce.py, which the package may not import."""
    spec = importlib.util.spec_from_file_location(
        "trace_reduce", os.path.join(BENCH, "trace_reduce.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pb2():
    mod = cs.load_pb2()
    if mod is None:
        pytest.skip("no xplane_pb2 module imports here")
    return mod


class _PlaneBuilder:
    """An XPlane written event by event (times in ms from the line's zero)."""

    def __init__(self, space, name: str):
        self.pl = space.planes.add()
        self.pl.name = name
        self._lines: dict = {}

    def _stat_id(self, name: str) -> int:
        for k, v in self.pl.stat_metadata.items():
            if v.name == name:
                return k
        k = len(self.pl.stat_metadata) + 1
        self.pl.stat_metadata[k].id = k
        self.pl.stat_metadata[k].name = name
        return k

    def _set(self, owner, stats: dict) -> None:
        for key, val in stats.items():
            st = owner.stats.add()
            st.metadata_id = self._stat_id(key)
            if isinstance(val, str):
                st.str_value = val
            else:
                st.int64_value = val

    def meta(self, name: str, **stats) -> int:
        k = len(self.pl.event_metadata) + 1
        self.pl.event_metadata[k].id = k
        self.pl.event_metadata[k].name = name
        self._set(self.pl.event_metadata[k], stats)
        return k

    def event(self, line: str, name_or_id, start_ms: float, dur_ms: float,
              **stats) -> None:
        if line not in self._lines:
            ln = self.pl.lines.add()
            ln.name, ln.timestamp_ns = line, 1_000_000
            self._lines[line] = ln
        mid = (name_or_id if isinstance(name_or_id, int)
               else self.meta(name_or_id))
        ev = self._lines[line].events.add()
        ev.metadata_id = mid
        ev.offset_ps, ev.duration_ps = int(start_ms * MS), int(dur_ms * MS)
        self._set(ev, stats)


def _synthetic(pb2):
    """Two programs of two kinds 10 ms apart, the gap under a `step:sync`
    span; operations whose paths nest `attn_gate` in `attn_qkv`, one with no
    scope, one Pallas kernel, one `while` around two of them."""
    space = pb2.XSpace()
    dev = _PlaneBuilder(space, "/device:TPU:0")
    dev.event("XLA Modules", "jit_step_fn(11)", 0, 10)
    dev.event("XLA Modules", "jit__rng_split(3)", 12, 0.5)  # glue
    dev.event("XLA Modules", "jit_decode_window_fn(7)", 20, 8)
    chunk = "jit(step_fn)/jit(main)/"
    win = "jit(decode_window_fn)/jit(main)/while/body/"
    qkv = dev.meta("%fusion.3 = bf16[64,512]{1,0} fusion(%p.1), kind=kOutput",
                   tf_op=chunk + "attn_qkv/dot_general")
    gate = dev.meta("%fusion.4 = f32[64,8]{1,0} fusion(%p.2), kind=kLoop",
                    tf_op=chunk + "attn_qkv/attn_gate/logistic")
    kern = dev.meta(
        "%paged_prefill_attention.2 = bf16[64,8,64]{2,1,0} custom-call(%q),"
        " custom_call_target=\"tpu_custom_call\"",
        tf_op=chunk + "attn/pallas_call")
    bare = dev.meta("%copy.9 = bf16[64,512]{0,1} copy(%x)",
                    tf_op=chunk + "transpose")
    loop = dev.meta("%while.1 = (s32[], bf16[4,512]) while(%t), body=%b",
                    tf_op="jit(decode_window_fn)/jit(main)/while")
    mlp = dev.meta("%fusion.21 = bf16[4,512]{1,0} fusion(%h), kind=kOutput",
                   tf_op=win + "mlp/dot_general")
    samp = dev.meta("%fusion.30 = s32[4]{0} fusion(%l), kind=kLoop",
                    tf_op=win + "sample/argmax")
    dev.event("XLA Ops", qkv, 0, 3)
    dev.event("XLA Ops", gate, 3, 1)
    dev.event("XLA Ops", kern, 4, 4)
    dev.event("XLA Ops", bare, 8, 2)
    dev.event("XLA Ops", "%fusion.77 = u32[2]{0} fusion()", 12, 0.5)
    dev.event("XLA Ops", loop, 20, 8)  # spans the three below
    dev.event("XLA Ops", mlp, 20, 3)
    dev.event("XLA Ops", mlp, 24, 3)  # 1 ms idle inside the program
    dev.event("XLA Ops", samp, 27, 1)
    host = _PlaneBuilder(space, "/host:CPU")
    for ph, s, d, seq, kind in (
            ("build", -2, 2, 1, "prefill"), ("post", 1, 18, 1, "prefill"),
            ("sync", 2, 16, 1, "prefill"),  # inside post: 2 .. 18
            ("sched", 19, 0.4, 2, ""), ("build", 19.4, 0.5, 2, "decode")):
        host.event("engine-step", "step:" + ph, s, d, seq=seq, kind=kind)
    host.event("engine-step", "dev:dry", 12, 0, seq=1, slack_ns=500_000)
    host.event("engine-step", "python_noise", 0, 30)
    host.event("main", "clock_pair", -1, 0, monotonic_ns=77, time_ns=99)
    return space


def test_synthetic_capture_reduces_to_the_expected_document(pb2):
    doc = cs.summarize(_synthetic(pb2))
    assert doc["chips"] == 1 and doc["events"] == 9
    assert doc["window_s"] == pytest.approx(0.028)
    # the `while` counts in busy time (its 1 ms hole is idle INSIDE a
    # program, which no gap shows), not in any row
    assert doc["busy_s"] == pytest.approx(0.0185)
    assert doc["programs"] == {
        "prefill": {"n": 1.0, "device_s": 0.01},
        "glue_program": {"n": 1.0, "device_s": 0.0005},
        "window": {"n": 1.0, "device_s": 0.008},
    }
    assert sorted(doc["scopes"]) == sorted([
        ["prefill", "proj", "attn_qkv", 0.003, 1.0],
        ["prefill", "glue", "attn_gate", 0.001, 1.0],  # innermost wins
        ["prefill", "other", "other", 0.002, 1.0],
        ["glue_program", "other", "other", 0.0005, 1.0],
        ["window", "mlp", "mlp", 0.006, 2.0],
        ["window", "ends", "sample", 0.001, 1.0],
    ])
    assert doc["kernels"] == [
        ["prefill", "paged_prefill_attention", 0.004, 1.0, "mixer"]]
    assert doc["unscoped"] == [["prefill", "copy", 0.002],
                               ["glue_program", "fusion", 0.0005]]
    # every operation once: the rows and the kernels sum to the operations'
    # seconds outside the container
    assert sum(r[3] for r in doc["scopes"]) + sum(
        r[2] for r in doc["kernels"]) == pytest.approx(0.0175)
    # one gap, from the chunk's end to the window's start, the glue program
    # inside it; most of it under `sync` (10 -> 18 of 10 ms), seen dry 2 ms
    # after the chunk's true end
    assert doc["idle_gaps"] == [[0.01, "prefill", "sync", 2.0]]
    idle = doc["idle"]
    assert idle["gaps_n"] == 1 and idle["gaps_s"] == pytest.approx(0.01)
    assert idle["by_phase_s"] == pytest.approx(
        {"sync": 0.008, "post": 0.001, "sched": 0.0004, "build": 0.0005,
         "none": 0.0001})
    assert sum(idle["by_phase_s"].values()) == pytest.approx(idle["gaps_s"])
    # dry from its sighting (12) to the step's own resolve (18), then
    # starved until the next dispatch's build ends (19.9)
    assert doc["host_account"] == {
        "starved_s": 0.0019, "dry_s": 0.006, "dry_slack_s": 0.0005,
        "dry_n": 1}
    assert doc["clock_pair"]["monotonic_ns"] == 77
    assert doc["step_spans"] == 5
    assert len(json.dumps(doc)) < 4096  # no per-event list


def test_two_chips_are_averaged(pb2):
    space = pb2.XSpace()
    for chip, dur in ((0, 4), (1, 2)):
        dev = _PlaneBuilder(space, f"/device:TPU:{chip}")
        dev.event("XLA Modules", "jit_step_fn(1)", 0, 6)
        op = dev.meta("%fusion.1 = f32[] fusion()",
                      tf_op="jit(step_fn)/mlp/dot_general")
        dev.event("XLA Ops", op, 0, dur)
    doc = cs.summarize(space)
    assert doc["chips"] == 2
    assert doc["programs"]["prefill"] == {"n": 1.0, "device_s": 0.006}
    assert doc["scopes"] == [["prefill", "mlp", "mlp", 0.003, 1.0]]
    assert doc["busy_s"] == pytest.approx(0.003)


def test_summary_agrees_with_trace_reduce_on_its_fixture(pb2):
    """The benchmark's reducer and the engine's read one capture alike:
    window, busy seconds, a program's seconds, an operation's."""
    from google.protobuf import text_format
    from jax.profiler import ProfileData

    with open(os.path.join(BENCH, "tests", "fixtures",
                           "small.xplane.txt")) as f:
        text = f.read()
    tr = _trace_reduce()
    theirs = tr.reduce_events(*tr.planes_of(ProfileData.from_text_proto(text)))
    ours = cs.summarize(text_format.Parse(text, pb2.XSpace()))
    assert ours["chips"] == theirs["chips"]
    assert ours["window_s"] == pytest.approx(theirs["window_s"])
    assert ours["busy_s"] == pytest.approx(theirs["busy_s"])
    mods = dict(theirs["modules"])
    assert ours["programs"]["window"]["device_s"] == pytest.approx(
        mods["jit_decode_window_fn"])
    assert ours["programs"]["prefill"]["device_s"] == pytest.approx(
        mods["jit_step_fn"])
    by_family: dict = {}
    for kind, family, seconds in ours["unscoped"]:  # the fixture has no tf_op
        by_family[family] = by_family.get(family, 0.0) + seconds
    assert by_family == pytest.approx(dict(theirs["device_ops"]))


@pytest.mark.parametrize("name", [
    "%fusion.12.3", "fusion.7", "copy.3", "paged_decode_attention.392",
    "%paged_decode_attention.392 = bf16[16,32,128]{2,1,0} custom-call(%a)",
    "%while.1 = (s32[], bf16[4,512]) while(%t), body=%b", "conditional.4",
    "call", "convert_multiply_fusion.22:5", "all-reduce.2", "",
])
def test_op_family_is_the_benchmarks(name):
    tr = _trace_reduce()
    assert cs.op_family(name) == tr.op_family(name)
    assert (cs.op_family(name) in cs.CONTAINERS) == (
        tr.op_family(name) in tr._CONTAINERS)


@pytest.mark.parametrize("name", [
    "jit_step_fn(99)", "jit_decode_window_fn(1234567)", "jit_bd_window_fn",
    "jit__chain_fn.3(12)", " jit_verify_fn (1)"])
def test_module_family_is_the_benchmarks(name):
    assert cs.module_family(name) == _trace_reduce().module_family(name)


@pytest.mark.parametrize("intervals", [
    [(0, 2), (1, 3), (5, 6)], [], [(1, 1)], [(0, 10), (2, 3), (4, 12)],
    [(5, 6), (0, 1), (0.5, 5.5)]])
def test_union_seconds_is_the_benchmarks(intervals):
    assert cs.union_seconds(intervals) == \
        _trace_reduce().union_seconds(intervals)
    assert cs.CONTAINERS == _trace_reduce()._CONTAINERS


@pytest.mark.parametrize("path, scope", [
    ("jit(step_fn)/jit(main)/attn_qkv/dot_general", "attn_qkv"),
    ("jit(step_fn)/jit(main)/attn_qkv/attn_gate/logistic", "attn_gate"),
    ("jit(step_fn)/attn_out/mla_absorb/dot_general", "mla_absorb"),
    ("jit(decode_window_fn)/while/body/closed_call/slice", None),
    ("jit(step_fn)/mlp/moe_dispatch/sort:", "moe_dispatch"),
    ("", None),
])
def test_innermost_registered_scope_wins(path, scope):
    assert cs.scope_of(path) == scope


def test_program_kinds():
    assert cs.program_kind("jit_step_fn(3)", 5e6) == "prefill"
    assert cs.program_kind("jit_sp_step_fn(3)", 5e6) == "prefill"
    assert cs.program_kind("jit_bd_window_fn(3)", 5e6) == "window"
    assert cs.program_kind("jit_verify_fn(3)", 5e6) == "verify"
    assert cs.program_kind("jit_step_fn(3)", 0.9e6) == "glue_program"
    assert cs.program_kind("jit__embed_fn(3)", 5e6) == "other_program"


def test_self_segments_give_each_instant_to_the_innermost_span():
    steps = [("build", 0, 10, 1, ""), ("h2d", 2, 4, 1, ""),
             ("enqueue", 5, 8, 1, ""), ("post", 12, 20, 1, ""),
             ("sync", 12, 18, 1, "")]
    segs = cs.self_segments(steps)
    assert segs == [(0, 2, "build"), (2, 4, "h2d"), (4, 5, "build"),
                    (5, 8, "enqueue"), (8, 10, "build"), (12, 18, "sync"),
                    (18, 20, "post")]
    starts = [s[0] for s in segs]
    assert cs.phases_inside(segs, starts, 3, 13) == {
        "h2d": 1, "build": 3, "enqueue": 3, "sync": 1}
    assert cs.phases_inside(segs, starts, 10, 12) == {}
    assert cs.phases_inside(segs, starts, -5, 0) == {}


# -- the registry ------------------------------------------------------------

def _scope_literals() -> dict[str, list[str]]:
    """Every `named_scope("<literal>")` call under the package -> where."""
    found: dict = {}
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", getattr(
                        node.func, "id", None)) == "named_scope"):
                assert len(node.args) == 1 and isinstance(
                    node.args[0], ast.Constant), (
                    f"{path}:{node.lineno}: a scope's name is a literal, so "
                    "that the registry can hold it")
                found.setdefault(node.args[0].value, []).append(
                    f"{os.path.relpath(path, ROOT)}:{node.lineno}")
    return found


def test_every_named_scope_is_registered_and_every_key_is_used():
    used = _scope_literals()
    assert set(used) - set(mc.DEVICE_SCOPES) == set(), (
        "scopes the programs name that metrics_contract.DEVICE_SCOPES does "
        f"not: { {k: used[k] for k in set(used) - set(mc.DEVICE_SCOPES)} }")
    assert set(mc.DEVICE_SCOPES) - set(used) == set()
    assert len(mc.DEVICE_SCOPES) >= 34


def test_every_scope_has_one_known_group():
    assert set(mc.DEVICE_SCOPES.values()) == set(
        mc.DEVICE_SCOPE_GROUPS) - {"other"}
    assert mc.DEVICE_SCOPE_GROUPS[-1] == "other"
    assert "other" not in mc.DEVICE_SCOPES


def test_program_kinds_name_functions_the_runner_jits():
    path = os.path.join(PACKAGE, "engine", "model_runner.py")
    with open(path) as f:
        defs = {n.name for n in ast.walk(ast.parse(f.read()))
                if isinstance(n, ast.FunctionDef)}
    for family in mc.DEVICE_PROGRAM_KINDS:
        assert family.startswith("jit_") and family[4:] in defs, family
    assert set(mc.DEVICE_PROGRAM_KINDS.values()) == {
        "prefill", "window", "verify"}


# -- the command line --------------------------------------------------------

def _run_module(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, "-m",
         "vllm_production_stack_tpu.engine.capture_summary", *argv],
        env=env, capture_output=True, text=True, timeout=120)


def test_module_reduces_a_directory_from_the_command_line(pb2, tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(_synthetic(pb2).SerializeToString())
    out = tmp_path / "out.json"
    proc = _run_module(str(tmp_path), str(out))
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(out.read_text())
    assert doc["dir"] == str(tmp_path)
    assert doc["file"] == "plugins/profile/2026_01_01/vm.xplane.pb"
    assert doc["programs"]["prefill"]["n"] == 1.0 and doc["reduce_s"] >= 0
    assert json.loads(proc.stdout)["chips"] == 1


def test_module_exits_non_zero_where_there_is_no_capture(tmp_path):
    proc = _run_module(str(tmp_path), str(tmp_path / "out.json"))
    assert proc.returncode == 1 and "no .xplane.pb" in proc.stderr
    assert not (tmp_path / "out.json").exists()


# -- the debug surface -------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import EngineServer

    return EngineServer(LLMEngine(EngineConfig.tiny()),
                        served_model_name="tiny-llama")


def _with_client(srv, coro_fn):
    async def runner():
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


def test_summary_is_404_before_any_capture(server):
    async def go(client):
        r = await client.get("/debug/profile/summary")
        programs = await (await client.get("/debug/programs")).json()
        index = await (await client.get("/debug")).json()
        return r.status, programs, index

    status, programs, index = _with_client(server, go)
    assert status == 404
    assert "last_capture" not in programs["device"]
    assert "GET /debug/profile/summary" in index["endpoints"]


def test_stop_answers_with_the_summary_and_programs_shows_it(
        server, tmp_path):
    """A CPU engine's capture has no device plane: the summary says so
    (`chips` 0) and still holds the host's part; the same document is the
    reply's, the summary endpoint's and `device.last_capture`."""
    async def go(client):
        r = await client.post("/debug/profile/start",
                              json={"dir": str(tmp_path)})
        assert r.status == 200, await r.text()
        await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello", "max_tokens": 4})
        stop = await client.post("/debug/profile/stop")
        again = await client.get("/debug/profile/summary")
        programs = await client.get("/debug/programs")
        return (stop.status, await stop.json(), await again.json(),
                await programs.json())

    status, reply, again, programs = _with_client(server, go)
    assert status == 200 and reply["status"] == "stopped"
    assert reply["dir"] == str(tmp_path)
    doc = reply["summary"]
    assert again == doc and programs["device"]["last_capture"] == doc
    assert doc["child_s"] >= 0
    if "error" in doc:  # no xplane_pb2 here: the capture is still there
        assert doc["dir"] == str(tmp_path)
    else:
        assert doc["chips"] == 0 and doc["programs"] == {}
        assert doc["step_spans"] > 0 and doc["clock_pair"]["monotonic_ns"]
        assert doc["reduce_s"] > 0 and doc["dir"] == str(tmp_path)
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)


@pytest.mark.parametrize("fault", ["exit_code", "timeout"])
def test_a_failed_child_leaves_the_dumps_directory_in_the_reply(
        server, tmp_path, monkeypatch, fault):
    """The capture is never lost to its summary: a child that exits
    non-zero (here: pointed at a directory that holds no capture) or runs
    out of its time leaves `error` and the reply is still 200."""
    dump, empty = tmp_path / "dump", tmp_path / "empty"
    empty.mkdir()
    if fault == "timeout":
        monkeypatch.setattr(server, "CAPTURE_SUMMARY_TIMEOUT_S", 0.001)

    async def go(client):
        r = await client.post("/debug/profile/start",
                              json={"dir": str(dump)})
        assert r.status == 200, await r.text()
        if fault == "exit_code":
            server._profile_dir = str(empty)
        stop = await client.post("/debug/profile/stop")
        programs = await client.get("/debug/programs")
        return stop.status, await stop.json(), await programs.json()

    status, reply, programs = _with_client(server, go)
    want = str(empty if fault == "exit_code" else dump)
    assert status == 200 and reply["dir"] == want
    assert reply["summary"]["dir"] == want
    assert ("exit code 1" if fault == "exit_code" else "Timeout") in \
        reply["summary"]["error"]
    assert programs["device"]["last_capture"] == reply["summary"]
    assert glob.glob(str(dump / "**" / "*.xplane.pb"), recursive=True)
