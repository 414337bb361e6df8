"""The multi-round-qa harness and request generator drive the real stack:
fake engines behind the router (the reference's CI rig shape,
router-e2e-test.yml:51-87)."""

import asyncio
import json
import subprocess
import sys

from aiohttp.test_utils import TestServer

from vllm_production_stack_tpu.router.app import build_app
from vllm_production_stack_tpu.router.args import parse_args
from vllm_production_stack_tpu.testing.fake_engine import FakeEngine


def _run_rig(script_args_fn):
    async def go():
        engines, servers = [], []
        for _ in range(2):
            eng = FakeEngine(model="fake-model", tokens_per_sec=5000)
            srv = TestServer(eng.build_app())
            await srv.start_server()
            engines.append(eng)
            servers.append(srv)
        urls = ",".join(f"http://127.0.0.1:{s.port}" for s in servers)
        router_srv = TestServer(build_app(parse_args([
            "--static-backends", urls,
            "--static-models", "fake-model;fake-model",
        ])))
        await router_srv.start_server()
        url = f"http://127.0.0.1:{router_srv.port}"
        try:
            proc = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: subprocess.run(
                    [sys.executable, *script_args_fn(url)],
                    capture_output=True, text=True, timeout=120,
                ),
            )
        finally:
            await router_srv.close()
            for s in servers:
                await s.close()
        return proc, engines

    return asyncio.run(go())


def test_multi_round_qa_against_router(tmp_path):
    out_csv = tmp_path / "out.csv"
    proc, engines = _run_rig(lambda url: [
        "benchmarks/multi_round_qa.py",
        "--base-url", url, "--model", "fake-model",
        "--num-users", "4", "--qps", "8", "--num-rounds", "2",
        "--system-prompt-len", "50", "--user-info-len", "50",
        "--answer-len", "16", "--duration", "6",
        "--output", str(out_csv),
    ])
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["requests_completed"] > 0
    assert summary["requests_failed"] == 0
    assert summary["gen_tok_per_s"] > 0
    assert summary["p50_ttft_s"] is not None
    # per-request CSV landed with the expected columns
    header = out_csv.read_text().splitlines()[0]
    assert header.startswith("user_id,round,launch_time,ttft")
    # load actually flowed through the router to the backends
    assert sum(e.total_requests for e in engines) >= summary[
        "requests_completed"
    ]


def test_request_generator_against_router():
    proc, engines = _run_rig(lambda url: [
        "benchmarks/request_generator.py",
        "--base-url", url, "--model", "fake-model",
        "--qps", "20", "--duration", "3",
    ])
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["errors"] == 0
    assert out["achieved_qps"] > 10


def test_sharegpt_mode_and_plot(tmp_path):
    """--sharegpt replaces synthetic questions with real conversation turns,
    and plot.py renders the sweep rows run.sh aggregates."""
    sharegpt = tmp_path / "sharegpt.json"
    sharegpt.write_text(json.dumps([
        {"conversations": [
            {"from": "human", "value": "What is the capital of France?"},
            {"from": "gpt", "value": "Paris."},
            {"from": "human", "value": "And of Italy?"},
        ]},
        {"conversations": [
            {"from": "user", "value": "Write a haiku about TPUs."},
        ]},
        {"conversations": [
            {"from": "gpt", "value": "no human turns here"},
        ]},
    ]))
    out_csv = tmp_path / "out.csv"
    proc, engines = _run_rig(lambda url: [
        "benchmarks/multi_round_qa.py",
        "--base-url", url, "--model", "fake-model",
        "--num-users", "2", "--qps", "8", "--num-rounds", "2",
        "--system-prompt-len", "32", "--answer-len", "8",
        "--duration", "4", "--sharegpt", str(sharegpt),
        "--output", str(out_csv),
    ])
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["requests_completed"] > 0
    assert summary["target_qps"] == 8.0
    # the engines saw the ShareGPT turns, not synthetic filler
    bodies = [
        r["body"] for e in engines for r in e.seen_request_log
        if "body" in r
    ]
    texts = json.dumps(bodies)
    assert "capital of France" in texts or "haiku about TPUs" in texts

    # plot.py consumes the per-QPS summaries run.sh writes
    results = tmp_path / "results"
    results.mkdir()
    for qps, ttft in ((0.5, 0.2), (1.0, 0.35), (2.0, 0.9)):
        (results / f"summary-qps{qps}.json").write_text(json.dumps({
            "target_qps": qps, "p50_ttft_s": ttft,
            "gen_tok_per_s": 1000 * qps,
        }))
    plot = subprocess.run(
        [sys.executable, "benchmarks/plot.py", str(results)],
        capture_output=True, text=True, timeout=120,
    )
    assert plot.returncode == 0, plot.stderr
    assert (results / "sweep.png").exists() or "printed rows" in plot.stderr
