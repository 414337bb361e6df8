#!/usr/bin/env python3
"""One cell of the chip benchmark: start the served stack (engine + router,
two processes), warm every shape the cell's traffic uses, measure for
`--seconds`, check the outputs, print one JSON line.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a data file or a small module found by the name in BENCHMARK.json (see
README.md). This process never imports JAX: the engine holds the chip.

`--rehearse` runs the same path on the CPU at a tiny size to find faults
before a chip call. It prints `device.platform: "cpu"`, no device metric,
and is never a measurement. Without it a run that finds no TPU, or fewer
chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

_T_PROC = __import__("time").monotonic()  # set-up is timed from here

import argparse
import asyncio
import importlib
import json
import os
import random
import shutil
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import client as client_mod  # noqa: E402
import stack  # noqa: E402
import stats  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from client import Client, text_of  # noqa: E402
from readers import _spans  # noqa: E402

REPO = stack.REPO
# chip_smoke.py's tolerance and its reason: reordered bf16 reductions move
# first-token logprobs (|lp| ~ ln V ~ 12) by a few hundredths of a nat
LOGPROB_ATOL = 0.25
MIN_SHARED_TOP8 = 7
TRACE_SECONDS = 8.0
DEADLINE_S = 1150.0  # a cell's first run compiles; the driver allows 1200 s


def log(obj: dict) -> None:
    """Everything but the result goes to stderr: stdout carries the one
    result line, or nothing when the run failed."""
    print(json.dumps(obj), file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Window:
    """The measured window. The generator opens it when its ramp is done;
    it lasts exactly `seconds`; hooks take the documents at both edges."""

    def __init__(self, seconds: float, on_open, on_close):
        self.seconds = seconds
        self.t_open: float | None = None
        self.t_close: float | None = None
        self._on_open, self._on_close = on_open, on_close
        self._closed = asyncio.Event()

    async def open(self) -> None:
        await self._on_open()
        self.t_open = time.monotonic()
        self.t_close = self.t_open + self.seconds
        asyncio.ensure_future(self._closer())

    async def _closer(self) -> None:
        await asyncio.sleep(self.t_close - time.monotonic())
        await self._on_close()
        self._closed.set()

    async def wait_closed(self) -> None:
        await self._closed.wait()

    def contains(self, t: float) -> bool:
        return self.t_open is not None and self.t_open <= t < self.t_close

    def overlaps(self, rec) -> bool:
        """Sent before the close and not ended before the open."""
        return (self.t_open is not None and rec.sent < self.t_close
                and (rec.done is None or rec.done >= self.t_open))


class Probe:
    """Documents of the engine at one moment."""

    def __init__(self, engine_url: str, cl: Client):
        self.url, self.cl = engine_url, cl

    async def take(self) -> dict:
        metrics, timing, programs = await asyncio.gather(
            self.cl.get_text(self.url + "/metrics"),
            self.cl.get_json(self.url + "/debug/timing"),
            self.cl.get_json(self.url + "/debug/programs"),
        )
        return {"t": time.monotonic(),
                "metrics": stack.parse_metrics(metrics),
                "timing": timing, "programs": programs}

    async def has_prefill_rows(self, rows: int, width_blocks: int) -> bool:
        """Is the prefill program of `rows` rows at this table width in the
        engine's inventory (compiled, compiling or loaded from the cache)?"""
        import ast
        doc = await self.cl.get_json(self.url + "/debug/programs")
        for p in doc["programs"]:
            k = ast.literal_eval(p["key"])
            if k[0] == "prefill" and k[1] == rows and k[3] == width_blocks \
                    and not any(k[4:]):
                return True
        return False

    async def wait_bg(self, timeout_s: float = 900.0) -> None:
        """Until no background build is pending in the engine."""
        t0 = time.monotonic()
        while True:
            t = await self.cl.get_json(self.url + "/debug/timing")
            if int(t["programs"]["bg_pending"]) == 0:
                return
            if time.monotonic() - t0 > timeout_s:
                raise TimeoutError("background compiles still pending")
            await asyncio.sleep(0.25)


async def warm_shapes(cl: Client, probe: Probe, warm: dict,
                      seed: int) -> dict:
    """Set-up, after the engine's own coarse warm-up: drive every program
    the window will use once, so that none compiles (or loads from the
    compile cache) inside it, and make the batch-invariance probe on the way.

    The coarse warm-up compiles only the widest block table; narrower tables
    pad up to it and their exact programs compile in the background on first
    use. So: a fixed prompt alone (logprobs), `holders` long requests, the
    same prompt again while they decode, bursts of 1/2/4/8 short prompts
    (the prefill row buckets); then wait until no background compile is
    pending."""
    rng = random.Random(seed ^ 0x5EED)
    t0 = time.monotonic()
    probe_prompt = "probe " + text_of(warm["row_prompt"], random.Random(7))
    alone = await cl.complete(probe_prompt, 1, kind="probe", logprobs=8)
    holders = [cl.launch(f"hold {i:04d} " + text_of(warm["holder_prompt"], rng),
                         warm["holder_tokens"], kind="warm")
               for i in range(warm["holders"])]
    while not all(r.chunk_times or t.done() for r, t in holders):
        await asyncio.sleep(0.01)
    crowded = await cl.complete(probe_prompt, 1, kind="probe", logprobs=8)
    await asyncio.gather(*(t for _, t in holders))
    # the bursts need free seats to land in one step, and a busy engine so
    # that they queue up together: one long "keeper" decodes meanwhile. A
    # burst is repeated until its program shows in the inventory.
    missing = []
    for rows in warm["prefill_rows"]:
        for attempt in range(4):
            keeper = cl.launch("keep " + text_of(warm["holder_prompt"], rng),
                               warm["holder_tokens"], kind="warm")
            while not (keeper[0].chunk_times or keeper[1].done()):
                await asyncio.sleep(0.01)
            burst = [cl.launch(f"row {rows:02d}.{attempt}.{j:02d} "
                               + text_of(warm["row_prompt"], rng), 2,
                               kind="warm") for j in range(rows)]
            await asyncio.gather(keeper[1], *(t for _, t in burst))
            await probe.wait_bg()  # a build shows in the inventory when done
            if await probe.has_prefill_rows(rows, warm["width_blocks"]):
                break
        else:
            missing.append(rows)
    waited = time.monotonic()
    await probe.wait_bg()
    bad = [r for r in cl.records if not r.ok]
    verdict = compare_top8(alone.logprobs, crowded.logprobs)
    verdict.update(warm_s=time.monotonic() - t0, warm_failed=len(bad),
                   bg_wait_s=time.monotonic() - waited,
                   rows_not_warmed=missing)
    if bad:
        verdict["first_error"] = bad[0].error or f"status {bad[0].status}"
    if missing:
        doc = await cl.get_json(probe.url + "/debug/programs")
        verdict["inventory"] = [p["key"] for p in doc["programs"]]
    return verdict


def compare_top8(a: dict | None, b: dict | None) -> dict:
    """Batch invariance: the same prompt alone and among the other seats' requests must
    give the same first-token distribution to within bf16 reordering."""
    if not a or not b:
        return {"probe_ok": False, "probe_error": "no logprobs returned"}
    shared = set(a) & set(b)
    worst = max((abs(a[t] - b[t]) for t in shared), default=float("inf"))
    return {"probe_ok": len(shared) >= MIN_SHARED_TOP8
            and worst <= LOGPROB_ATOL,
            "probe_shared_top8": len(shared), "probe_max_abs_diff": worst}


async def sample_gauges(cl: Client, engine_url: str, window: Window,
                        out: list) -> None:
    """Traced runs only: /metrics every 0.25 s inside the window (seat
    occupancy and KV usage are gauges; the end-to-end run leaves them be)."""
    while window.t_open is None:
        await asyncio.sleep(0.05)
    while time.monotonic() < window.t_close:
        try:
            out.append(stack.parse_metrics(
                await cl.get_text(engine_url + "/metrics")))
        except Exception as e:  # a missed sample is not a failed run
            log({"sample_error": f"{type(e).__name__}: {e}"[:200]})
        await asyncio.sleep(0.25)


async def trace_slice(cl: Client, engine_url: str, window: Window,
                      trace_dir: str, out: dict) -> None:
    """Traced runs only: an xprof capture of a few seconds in the middle of
    the window, taken by the process that holds the chip."""
    while window.t_open is None:
        await asyncio.sleep(0.05)
    span = min(TRACE_SECONDS, window.seconds / 2)
    await asyncio.sleep(max(0.0, (window.seconds - span) / 2))
    await cl.post_json(engine_url + "/debug/profile/start",
                       {"dir": trace_dir})
    out["t0"] = time.monotonic()
    out["timing0"] = await cl.get_json(engine_url + "/debug/timing")
    await asyncio.sleep(span)
    out["timing1"] = await cl.get_json(engine_url + "/debug/timing")
    out["t1"] = time.monotonic()
    await cl.post_json(engine_url + "/debug/profile/stop", {})
    out["stop_s"] = time.monotonic() - out["t1"]


async def session(args, cell, config, spec, urls, out_dir) -> dict:
    """Everything that talks HTTP: warm, ramp, window, drain."""
    router_url, engine_url = urls
    gen_mod = traffic_mod.load_generator(spec)
    res: dict = {"samples": [], "trace": {}}
    async with Client(router_url, config["model"]) as cl:
        probe = Probe(engine_url, cl)
        warm = spec["rehearse_warm"] if args.rehearse else spec["warm"]
        res["warm"] = await warm_shapes(cl, probe, warm, args.seed)
        log({"phase": "warm", **res["warm"]})
        n_warm = len(cl.records)

        async def on_open():
            res["start"] = await probe.take()

        async def on_close():
            res["end"] = await probe.take()

        gen = gen_mod.Generator(spec["params"], args.seed)
        window = Window(float(args.seconds), on_open, on_close)
        side = []
        if args.trace:
            side = [asyncio.ensure_future(sample_gauges(
                        cl, engine_url, window, res["samples"])),
                    asyncio.ensure_future(trace_slice(
                        cl, engine_url, window,
                        os.path.join(out_dir, "xprof"), res["trace"]))]
        await gen.run(cl, window)
        for t in side:
            await t
        res["records"] = cl.records[n_warm:]
        res["window"] = window
        res["final"] = await probe.take()
    return res


def end_to_end(records, window, setup_s: float) -> tuple[dict, dict]:
    """The end-to-end metrics, all from the client's clock, and beside them
    the median token gap (a per-layer metric reads it). Returns values and
    the sample counts behind them; a percentile without its ten samples
    beyond is still computed, and named in `short`."""
    counted = [r for r in records if r.counted]
    done_in = [r for r in counted
               if r.done is not None and window.contains(r.done)]
    tpot = [r.tpot() for r in done_in if r.tpot() is not None]
    tokens = sum(r.tokens_between(window.t_open, window.t_close)
                 for r in records)
    values = {"setup_s": setup_s, "out_tok_per_s": tokens / window.seconds}
    counts = {"counted": len(counted), "ok": sum(r.ok for r in counted),
              "tokens_in_window": tokens, "tpot_samples": len(tpot),
              "short": []}
    try:
        values["tpot_p50_ms"] = stats.percentile(tpot, 50) * 1e3
    except stats.TooFewSamples as e:
        counts["short"].append(f"tpot_p50_ms: {e}")
        if tpot:
            values["tpot_p50_ms"] = stats.percentile(
                tpot, 50, min_beyond=0) * 1e3
    return values, counts


def reduce_trace(trace_dir: str, out_path: str) -> dict | None:
    """xplane -> busy/idle and per-op seconds, in a child that may import
    JAX (on the CPU backend: the engine has let go of the chip by now, and
    this reads a file)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         trace_dir, out_path],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        log({"trace_reduce_failed": proc.stderr[-600:]})
        return None
    return load_json(out_path)


def cells_of(metric: dict, manifest: dict) -> list[str]:
    """The cells that report a metric: its `workloads` list, or every cell.
    BENCHMARK.json alone says so; no file under this directory repeats it."""
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def read_per_layer(manifest, cell, ctx, failures: list[str]) -> dict:
    """Every per-layer metric of this cell through its own reader
    (`metrics/<name>.json` names `readers/<reader>.py` and its arguments).
    A reader that finds nothing to read (no such counter, no such kernel in
    the trace) returns None: the metric is left out of the line and named on
    stderr. A reader that raises is a fault, and the run is not correct."""
    out, absent = {}, []
    for m in manifest["per_layer"]:
        if cell["name"] not in cells_of(m, manifest):
            continue
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
        reader = importlib.import_module(f"readers.{spec['reader']}")
        try:
            value = reader.read(ctx, **spec.get("args", {}))
        except Exception as e:
            failures.append(f"reader of {m['name']} raised "
                            f"{type(e).__name__}: {e}"[:300])
            continue
        if value is None:
            absent.append(m["name"])
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    log({"phase": "per_layer", "read": sorted(out), "absent": absent})
    if not out:
        failures.append("no per-layer metric could be read")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, no device metric: not a measurement")
    args = ap.parse_args()

    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    config = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    spec = traffic_mod.load_traffic(cell["traffic"], args.rehearse)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    client_mod.DECODE_WINDOW = config["decode_window"]
    if args.rehearse:
        config = {**config, **config["rehearse"]}
    out_dir = os.path.join(
        REPO, "chiprun_out", "bench",
        f"{cell['name']}-s{args.seed}-t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    # the compile cache lives at one fixed path inside the checkout, whatever
    # the machine's environment says, and without a size cap: the step
    # programs are 90-180 MiB each, and under a cap below their sum an LRU
    # cache evicts every entry before the next run asks for it
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        # no cache on the CPU backend (utils/compile_cache.py: reading an
        # entry back crashes on this image)
        del env["JAX_COMPILATION_CACHE_DIR"]
        if cell["chips"] > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count="
                                f"{cell['chips']}").strip()
    engine_port, router_port = stack.free_port(), stack.free_port()
    engine_url = f"http://127.0.0.1:{engine_port}"
    router_url = f"http://127.0.0.1:{router_port}"
    engine = router = None
    result = None
    failures: list[str] = []
    try:
        # both at once: the router needs nothing from the engine to start
        engine = stack.Child(
            "engine", stack.engine_argv(config, args.seed, engine_port),
            out_dir, env)
        router = stack.Child(
            "router", stack.router_argv(config, engine_url, router_port),
            out_dir, env)
        boot_s = engine.wait_healthy(engine_url, DEADLINE_S - 120)
        router.wait_healthy(router_url, 120)
        programs = stack.http_json("GET", engine_url + "/debug/programs")
        dev = programs["device"]
        log({"phase": "engine_up", "boot_to_healthy_s": boot_s,
             "since_start_s": time.monotonic() - _T_PROC,
             "device": {k: dev.get(k) for k in
                        ("platform", "kind", "count", "mesh_device_ids",
                         "attention")},
             "pool_pages": config.get("num_blocks"),
             "warmup_programs": len(programs["programs"]),
             "warmup_compile_wall_s": sum(
                 p["compile_wall_s"] for p in programs["programs"]),
             "program_cache": programs["cache"]})
        want_platform = "cpu" if args.rehearse else "tpu"
        if dev["platform"] != want_platform or dev["count"] < cell["chips"]:
            raise RuntimeError(
                f"cell needs {cell['chips']} x {want_platform}, engine "
                f"reports {dev['count']} x {dev['platform']}")
        if not args.rehearse and dev["kind"] not in peaks:
            raise RuntimeError(f"device kind {dev['kind']!r} is not in "
                               "peaks.json: add it with its source")

        res = asyncio.run(session(
            args, cell, config, spec, (router_url, engine_url), out_dir))
        window = res["window"]
        setup_s = window.t_open - _T_PROC
        values, counts = end_to_end(res["records"], window, setup_s)
        log({"phase": "window", "setup_s": setup_s,
             "t_open": window.t_open, "t_close": window.t_close,
             "setup_split": {"engine_start_s": boot_s,
                             "warm_s": res["warm"]["warm_s"],
                             "ramp_s": setup_s - boot_s - res["warm"]["warm_s"]},
             **counts,
             "all": {k: v for k, v in values.items()}})

        # -- correctness -----------------------------------------------------
        counted = [r for r in res["records"] if r.counted]
        bad = [r for r in counted if not r.ok]
        c0, c1 = (res[k]["programs"]["compiles"] for k in ("start", "end"))
        compiled = {k: c1[k] - c0.get(k, 0) for k in c1
                    if c1[k] != c0.get(k, 0)}
        fb = (res["end"]["programs"]["failed_builds"]["total"]
              - res["start"]["programs"]["failed_builds"]["total"])
        if bad:
            failures.append(f"{len(bad)} counted requests failed; first: "
                            f"{bad[0].error or bad[0].usage_completion}")
        prog0, prog1 = (res[k]["timing"]["programs"] for k in ("start", "end"))
        fell_back = prog1["compile_fallbacks"] - prog0["compile_fallbacks"]
        if fell_back or prog0["bg_pending"] or prog1["bg_pending"]:
            # a shape met a program that was not there: it was padded up to
            # a larger one and its own build queued in the background (where
            # it waits for a lull in traffic, up to ten minutes)
            failures.append(
                f"{fell_back} pad-up fallbacks inside the window; background "
                f"builds pending at its open {prog0['bg_pending']}, at its "
                f"end {prog1['bg_pending']}")
        if compiled or fb:
            had = {p["key"] for p in res["start"]["programs"]["programs"]}
            new = [(p["key"], p["trigger"])
                   for p in res["end"]["programs"]["programs"]
                   if p["key"] not in had]
            failures.append(f"compiles inside the window: {compiled}, "
                            f"failed builds {fb}, new programs {new}")
        if (not res["warm"]["probe_ok"] or res["warm"]["warm_failed"]
                or res["warm"]["rows_not_warmed"]):
            failures.append(f"set-up probe or warm requests failed: "
                            f"{res['warm']}")
        if not counted:
            failures.append("no counted request")

        final_dev = res["final"]["programs"]["device"]
        device = {
            "platform": final_dev["platform"], "kind": final_dev["kind"],
            "count": final_dev["count"],
            "memory_peak_bytes": max(
                (d.get("peak_bytes_in_use", 0)
                 for d in final_dev["devices"]), default=0),
        }
    except Exception as e:
        failures.append(f"{type(e).__name__}: {e}"[:2000])
        log({"phase": "error", "trace": traceback.format_exc()[-1500:]})
        res = None
    finally:
        codes = {c.name: c.stop() for c in (router, engine) if c is not None}
        log({"phase": "shutdown", "exit_codes": codes})
    if res is None:
        log({"phase": "failed", "failures": failures})
        return 1
    if any(c != 0 for c in codes.values()):
        failures.append(f"a process did not exit 0 on SIGTERM: {codes}")

    cell_e2e = [m for m in manifest["end_to_end"]
                if cell["name"] in cells_of(m, manifest)]
    if not args.trace:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_e2e if m["name"] in values}
        for m in cell_e2e:
            if any(s.startswith(m["name"] + ":") for s in counts["short"]):
                failures.append(f"too few samples for {m['name']}")
        result = {"metrics": metrics}
    else:
        trace = None
        xprof = os.path.join(out_dir, "xprof")
        if os.path.isdir(xprof):
            trace = reduce_trace(xprof, os.path.join(out_dir, "trace.json"))
            shutil.rmtree(xprof, ignore_errors=True)  # hundreds of MB
        ctx = {"cell": cell, "config": config, "traffic": spec,
               "peaks": peaks.get(device["kind"]), "window": window,
               "start": res["start"], "end": res["end"],
               "samples": res["samples"], "records": res["records"],
               "values": values, "trace": trace,
               "trace_span": res["trace"], "device": final_dev}
        result = {"metrics": read_per_layer(manifest, cell, ctx, failures)}
        if trace is not None and not args.rehearse:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": trace["device_ops"][:10],
                "idle_gaps": _spans.idle_gaps(ctx)[:10],
            }
        elif not args.rehearse:
            failures.append("no device trace was read")
    if failures:
        log({"phase": "failures", "failures": failures})
    with open(os.path.join(out_dir, "programs.json"), "w") as f:
        json.dump(res["final"]["programs"], f)
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump([{k: v for k, v in vars(r).items() if k != "logprobs"}
                   for r in res["records"]], f)
    line = {"correct": not failures, "attempted": len(counted),
            "failed": len(bad), **result, "device": device}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
