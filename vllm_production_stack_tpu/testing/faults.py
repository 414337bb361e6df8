"""Deterministic fault injection for the request-lifecycle chaos suite.

The reference stack proves robustness claims operationally (kill a pod,
watch the router); this module makes the same faults injectable in-process
and DETERMINISTIC, driven through the real aiohttp wire — no mocks on the
client side, the router talks TCP to a server that misbehaves on cue:

- **ChaosEngine** — a FakeEngine whose streaming path can kill the
  connection abruptly mid-stream (`kill_after_chunks`), die after reading
  the request but before sending headers (`kill_before_headers`), or turn
  slow-loris (`stall_after_chunks`: send N chunks then hold the connection
  open sending nothing). Faults are plain instance attributes the test
  flips; every triggered fault is appended to `faults_fired` for
  assertions.
- **black_hole()** — a listener that accepts TCP and never writes a byte
  (the partition shape: connect succeeds, the request vanishes).
- **dead_port()** — a port with no listener (connect refused: the only
  fault the pre-chaos stack handled).

tests/test_chaos.py drives these against the real router app and asserts
the invariant this layer exists for: every request completes, fails over,
or gets ONE clean 4xx/5xx — never hangs, never silently drops.
"""

from __future__ import annotations

import asyncio
import json
import socket
import time
import uuid

from aiohttp import web

from .fake_engine import FakeEngine


class ChaosEngine(FakeEngine):
    """FakeEngine with injectable, deterministic wire-level faults."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        # fault knobs — set directly from tests; None/False = healthy
        self.kill_before_headers = False  # die after reading the body
        self.kill_after_chunks: int | None = None  # abrupt close mid-stream
        self.stall_after_chunks: int | None = None  # slow-loris
        self.stall_release = asyncio.Event()  # un-stalls held connections
        self.draining = False  # mimic a draining real engine
        self.faults_fired: list[str] = []

    def _kill(self, request: web.Request, label: str) -> None:
        """Abrupt TCP teardown — the client sees a dropped connection, not
        a clean HTTP close (SO_LINGER-style RST is not portable; closing
        the transport mid-response is close enough on loopback)."""
        self.faults_fired.append(label)
        if request.transport is not None:
            request.transport.close()

    async def h_completion(self, request: web.Request) -> web.StreamResponse:
        if self.draining:
            return web.json_response(
                {"error": {"message": "engine is draining",
                           "type": "service_unavailable"}},
                status=503,
                headers={"X-Engine-Draining": "1"},
            )
        body = await request.json()
        if self.kill_before_headers:
            # the request reached the engine (it may have been processed) —
            # a correct router must NOT resend it to another endpoint
            self._kill(request, "kill_before_headers")
            raise ConnectionResetError("chaos: killed before headers")
        if self.kill_after_chunks is None and self.stall_after_chunks is None:
            # healthy path: FakeEngine semantics, same accounting
            request = _replay_body(request, body)
            return await super().h_completion(request)
        # faulting stream path (mirrors FakeEngine's chunk shape)
        self.total_requests += 1
        self.seen_request_log.append(
            {"path": request.path, "body": body, "t": time.time()}
        )
        is_chat = request.path.endswith("chat/completions")
        n = int(body.get("max_tokens") or self.default_tokens)
        rid = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        gap = 1.0 / self.tokens_per_sec
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"}
        )
        await resp.prepare(request)
        self.running += 1
        try:
            for i in range(n):
                if self.kill_after_chunks is not None and i >= self.kill_after_chunks:
                    self._kill(request, "kill_after_chunks")
                    return resp
                if (
                    self.stall_after_chunks is not None
                    and i >= self.stall_after_chunks
                ):
                    # slow-loris: hold the connection open, send nothing
                    self.faults_fired.append("stall")
                    await self.stall_release.wait()
                    return resp
                await asyncio.sleep(gap)
                delta = (
                    {"delta": {"content": f"tok{i} "}}
                    if is_chat
                    else {"text": f"tok{i} "}
                )
                chunk = {
                    "id": rid, "created": created,
                    "object": ("chat.completion.chunk" if is_chat
                               else "text_completion"),
                    "model": body.get("model", self.model),
                    "choices": [{"index": 0, **delta, "finish_reason": None}],
                }
                await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            return resp
        except (ConnectionResetError, ConnectionError):
            return resp
        finally:
            self.running -= 1


class _ReplayRequest:
    """Minimal request view whose json() replays an already-read body (the
    chaos handler reads it to decide faults before delegating)."""

    def __init__(self, request: web.Request, body: dict):
        self._request = request
        self._body = body

    def __getattr__(self, name):
        return getattr(self._request, name)

    async def json(self):
        return self._body


def _replay_body(request: web.Request, body: dict) -> web.Request:
    return _ReplayRequest(request, body)  # type: ignore[return-value]


class hold_lock:
    """Context manager that HOLDS a threading lock for its whole scope —
    the deterministic deadlock-shape wedge (docs/37-flight-recorder.md):
    any loop that needs the lock (the hydration fetcher under the
    disk-tier lock, classically) blocks busy until the scope exits, and
    the thread-liveness watchdog must name it."""

    def __init__(self, lock):
        self.lock = lock

    def __enter__(self):
        self.lock.acquire()
        return self

    def __exit__(self, *exc):
        self.lock.release()
        return False


class frozen_step_loop:
    """Context manager that freezes an engine's step loop: ``engine.step``
    is wrapped to block on an event until the scope exits — the
    whole-engine wedge shape (collective stall / runaway compile under
    the engine lock). The step thread stops beating its heartbeat while
    frozen, so the watchdog must name thread=step."""

    def __init__(self, engine):
        self.engine = engine
        self._release = None

    def __enter__(self):
        import threading

        release = threading.Event()
        real_step = self.engine.step

        def frozen(*a, **kw):
            release.wait()
            return real_step(*a, **kw)

        self.engine.step = frozen
        self._release = (release, real_step)
        return self

    def __exit__(self, *exc):
        release, real_step = self._release
        self.engine.step = real_step
        release.set()  # unblock a step thread parked inside the wrapper
        return False


async def black_hole() -> tuple[asyncio.AbstractServer, int]:
    """A listener that accepts connections and never responds — the
    network-partition shape (connect succeeds; the request vanishes).
    Caller closes the returned server."""

    async def swallow(reader, writer):
        try:
            while await reader.read(65536):
                pass
        except Exception:
            pass
        finally:
            # the peer is gone: release the transport, or the caller's
            # server.wait_closed() waits for it forever (Python >= 3.12.1
            # waits for every accepted connection to close)
            writer.close()

    server = await asyncio.start_server(swallow, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    return server, port


def dead_port() -> int:
    """A loopback port with nothing listening (connect refused)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
