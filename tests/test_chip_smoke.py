"""chip_smoke.py cannot pass without the chip, the compile cache goes where
the caller puts it, and the engine says what it runs on.

The one slow test boots the real engine and router as OS processes (tiny
model, CPU) through chip_smoke.py itself: every request is served, and the
run still FAILS — exit code, last line and named platform — because the
platform it found is not `tpu`.
"""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from vllm_production_stack_tpu.engine import memory
from vllm_production_stack_tpu.engine.compile_watch import CompileWatch
from vllm_production_stack_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a) the smoke itself, held to the CPU ------------------------------------

def test_chip_smoke_drives_every_request_and_fails_without_a_tpu():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # one CPU device, as one chip
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--model", "tiny-llama"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}

    # it cannot pass: exit code, last line, and the platform it names
    assert proc.returncode != 0
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert any("platform 'cpu'" in f for f in phases["failures"]["failures"])
    assert phases["kernels"]["ok"] is False  # Mosaic kernels ran nowhere

    # ...although the whole served path ran: two processes, seven requests
    up = phases["engine_up"]
    assert up["ok"] is False and up["warmup_programs"] > 0
    served = {r["request"]: r for r in phases["requests"]["served"]}
    assert sorted(served) == [
        "chat", "chat_stream", "completion_0", "completion_1",
        "completion_2", "completion_3", "prefix_repeat",
    ]
    assert served["chat_stream"]["tokens"] >= 64
    assert served["completion_0"]["prompt_tokens"] >= 1024
    assert phases["requests"]["prefix_hit_blocks"] * 32 >= 1024
    assert phases["requests"]["engine_metric_samples"] > 0
    assert phases["requests"]["router_metric_samples"] > 0
    after = phases["after_traffic"]
    assert after["mid_traffic_compiles"] == 0 and after["failed_builds"] == 0
    assert phases["shutdown"]["exit_codes"] == {"router": 0, "engine": 0}

    # (c) over HTTP: what GET /debug/programs "device" carries
    dev = up["device"]
    assert (dev["platform"], dev["kind"], dev["count"]) == ("cpu", "cpu", 1)
    assert dev["mesh_device_ids"] == [0] and dev["devices"] == [{"id": 0}]
    assert dev["attention"]["decode"] == {"requested": "auto",
                                          "resolved": "xla"}
    assert dev["kvhash"] in ("native", "python")


@pytest.mark.parametrize("module", ["chip_smoke"])
def test_orchestrators_stay_off_jax(module):
    """A parent that starts the processes that hold the chip must not import
    JAX itself: one process per chip."""
    code = (f"import sys, {module}; "
            "sys.exit(any(m.split('.')[0] in ('jax', 'jaxlib') "
            "for m in sys.modules))")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=60).returncode == 0


# -- (b) the compile-cache helper ----------------------------------------------

@pytest.fixture
def jax_cache_config():
    """Restore the process-wide cache settings the helper touches (a cache
    left ON would crash later compiles: tests/conftest.py)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def _placed_from_outside(path: str) -> bool:
    return ("tmp" not in path.split(os.sep)
            and str(os.getpid()) not in path)


def test_cache_dir_env_wins_and_flag_is_ignored(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/var/cache/xla")
    assert compile_cache.compile_cache_dir() == "/var/cache/xla"
    assert compile_cache.compile_cache_dir("/flag/dir") == "/var/cache/xla"
    assert compile_cache.compile_cache_dir("") == "/var/cache/xla"


def test_cache_dir_flag_then_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.compile_cache_dir("/flag/dir") == "/flag/dir"
    assert compile_cache.compile_cache_dir("") is None  # explicit off
    fixed = compile_cache.compile_cache_dir()
    assert fixed == os.path.join(REPO, ".jax_cache")
    assert fixed == compile_cache.compile_cache_dir(None)  # never varies
    assert _placed_from_outside(fixed)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_configure_sets_no_directory_when_env_is_set(
        monkeypatch, jax_cache_config):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(compile_cache.ENV_VAR, "/var/cache/xla")
    warnings = []
    monkeypatch.setattr(compile_cache.logger, "warning",
                        lambda msg, *a: warnings.append(msg % a))
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.configure_compile_cache("/flag/dir")
    assert got == "/var/cache/xla"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert len(warnings) == 1 and "/flag/dir" in warnings[0]
    assert compile_cache.configure_compile_cache() == "/var/cache/xla"
    assert len(warnings) == 1  # no flag, nothing to warn about


@pytest.mark.parametrize("flag,want", [
    ("/flag/dir", "/flag/dir"),
    (None, os.path.join(REPO, ".jax_cache")),
    ("", None),
])
def test_configure_without_env_uses_flag_else_fixed_path(
        monkeypatch, jax_cache_config, flag, want):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache(flag) == want
    assert jax.config.jax_compilation_cache_dir == (want or before)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_configure_turns_the_cache_off_on_the_cpu_backend(
        monkeypatch, jax_cache_config):
    """CPU subprocesses that boot the real server must end up with the
    cache off, whatever the flag or the environment says."""
    monkeypatch.setenv(compile_cache.ENV_VAR, "/var/cache/xla")
    assert jax.default_backend() == "cpu"
    assert compile_cache.configure_compile_cache("/flag/dir") is None
    assert jax.config.jax_enable_compilation_cache is False


# -- (c) the device report, and memory that is never assumed ------------------

class _FakeDevice:
    def __init__(self, id_, platform="tpu", stats=None):
        self.id, self.platform = id_, platform
        self.device_kind = "TPU v5 lite"
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_report_names_platform_kind_count_ids_and_bytes():
    stats = {"bytes_in_use": 5, "peak_bytes_in_use": 7, "bytes_limit": 11,
             "num_allocs": 3}
    mesh = types.SimpleNamespace(
        devices=np.array([_FakeDevice(2, stats=stats),
                          _FakeDevice(3, stats=stats)], dtype=object),
        shape={"dp": 1, "tp": 2},
    )
    report = memory.device_report(mesh)
    assert report["platform"] == jax.devices()[0].platform
    assert report["kind"] == jax.devices()[0].device_kind
    assert report["count"] == len(jax.devices())
    assert report["mesh_device_ids"] == [2, 3]
    assert report["mesh_shape"] == {"dp": 1, "tp": 2}
    assert report["devices"] == [
        {"id": i, "bytes_in_use": 5, "peak_bytes_in_use": 7,
         "bytes_limit": 11} for i in (2, 3)
    ]
    json.dumps(report)  # served as JSON


def test_hbm_size_is_the_devices_own_or_an_error(monkeypatch):
    # the CPU backend reports nothing: tests get the stand-in
    assert memory.device_hbm_bytes() == memory.CPU_ASSUMED_HBM_BYTES
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _FakeDevice(0, stats={"bytes_limit": 123})])
    assert memory.device_hbm_bytes() == 123
    # an accelerator that does not say is an error, never 16 GiB
    for stats in (None, {}, {"bytes_in_use": 1}):
        monkeypatch.setattr(jax, "local_devices", lambda s=stats: [
            _FakeDevice(0, stats=s)])
        with pytest.raises(RuntimeError, match="bytes_limit"):
            memory.device_hbm_bytes()


def test_failed_builds_are_counted_in_the_program_inventory():
    watch = CompileWatch()
    assert watch.debug_payload()["failed_builds"] == {"total": 0,
                                                      "last": None}
    key = ("decode", 4, 128, 8, False, False, None)
    watch.record_failure("decode", key, "bg", RuntimeError("vmem"))
    failed = watch.debug_payload()["failed_builds"]
    assert failed["total"] == 1
    assert failed["last"]["key"] == repr(key)
    assert failed["last"]["error"] == "RuntimeError: vmem"
