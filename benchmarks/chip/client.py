"""The load generator's client: one asyncio process, streaming completions
through the router, every chunk's arrival on the monotonic clock.

Tokens are counted at the client. The server's SSE chunks carry no token
count, so a request's `max_tokens` tokens (all are produced: `ignore_eos`)
are laid on its token-bearing chunks: the first chunk is the prefill's one
token, the rest are spread evenly over the later chunks (one chunk is one
fused decode window), and each chunk's tokens over the time since the
stream's previous chunk (`Record.tokens_between`).
`usage.completion_tokens` of the closing chunk must equal `max_tokens`, or
the request counts as failed.
"""

from __future__ import annotations

import asyncio
import json
import random
import time
from dataclasses import dataclass, field

import aiohttp

_ALPHABET = "abcdefghijklmnopqrstuvwxyz "


def text_of(n_tokens: int, rng: random.Random) -> str:
    """n printable ASCII characters: n tokens under the byte tokenizer the
    registry presets serve with."""
    return "".join(rng.choices(_ALPHABET, k=n_tokens))


@dataclass
class Record:
    """One request as the client saw it. Times are `time.monotonic()`."""
    rid: int
    kind: str                 # generator's label: "ramp", "chat", "warm"...
    prompt_tokens: int        # as generated (characters = tokens)
    max_tokens: int
    sent: float = 0.0
    chunk_times: list[float] = field(default_factory=list)
    done: float | None = None  # [DONE] seen
    status: int | None = None
    usage_prompt: int | None = None
    usage_completion: int | None = None
    error: str | None = None
    logprobs: dict | None = None  # first token's top logprobs, when asked
    counted: bool = False     # set by the generator: belongs to the window

    @property
    def ok(self) -> bool:
        return (self.error is None and self.status == 200
                and self.done is not None
                and self.usage_completion == self.max_tokens
                and bool(self.chunk_times))

    def tpot(self) -> float | None:
        """(t_last - t_first) / (n_out - 1), seconds."""
        if not self.ok or self.max_tokens < 2 or len(self.chunk_times) < 2:
            return None
        return ((self.chunk_times[-1] - self.chunk_times[0])
                / (self.max_tokens - 1))

    def tokens_between(self, t0: float, t1: float) -> float:
        """Output tokens produced in [t0, t1). A chunk is one fused decode
        window delivered at its end, so its tokens are spread evenly over
        the time since the stream's previous chunk; the first chunk (the
        prefill's one token) counts at its arrival. Counting whole chunks
        at arrival instead quantises a window's count by one engine cycle
        (rows x 8 tokens): runs of identical work then differ by +-1%."""
        total, prev = 0.0, None
        for t, n in zip(self.chunk_times, self.chunk_tokens()):
            if prev is None or t <= prev:
                total += n if t0 <= t < t1 else 0.0
            else:
                total += n * max(0.0, min(t, t1) - max(prev, t0)) / (t - prev)
            prev = t
        return total

    def chunk_tokens(self) -> list[float]:
        k = len(self.chunk_times)
        if k == 0:
            return []
        if k == 1:
            return [float(self.max_tokens)]
        if self.done is None:
            # unfinished stream: the chunks seen so far are full windows
            return [1.0] + [float(DECODE_WINDOW)] * (k - 1)
        return [1.0] + [(self.max_tokens - 1) / (k - 1)] * (k - 1)


# tokens per chunk of a stream that has not ended yet (the engine's fused
# decode window; set from the configuration by the harness)
DECODE_WINDOW = 8


class Client:
    def __init__(self, base_url: str, model: str):
        self.base_url = base_url
        self.model = model
        self.records: list[Record] = []
        self._session: aiohttp.ClientSession | None = None
        self._next_rid = 0

    async def __aenter__(self) -> "Client":
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=300),
        )
        return self

    async def __aexit__(self, *exc) -> None:
        await self._session.close()

    async def get_json(self, url: str) -> dict:
        async with self._session.get(url) as r:
            r.raise_for_status()
            return await r.json()

    async def get_text(self, url: str) -> str:
        async with self._session.get(url) as r:
            r.raise_for_status()
            return await r.text()

    async def post_json(self, url: str, body: dict) -> dict:
        async with self._session.post(url, json=body) as r:
            text = await r.text()
            if r.status != 200:
                raise RuntimeError(f"POST {url} -> {r.status}: {text[:300]}")
            return json.loads(text)

    def launch(self, prompt: str, max_tokens: int, *, kind: str,
               logprobs: int | None = None,
               ) -> tuple[Record, asyncio.Task]:
        """Start one streamed completion. The Record is live (its chunk
        times fill in as they arrive); the task ends when the stream does
        and never raises for a failed request: the failure is on the Record."""
        rec = Record(rid=self._next_rid, kind=kind, prompt_tokens=len(prompt),
                     max_tokens=max_tokens)
        self._next_rid += 1
        self.records.append(rec)
        body = {
            "model": self.model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "ignore_eos": True, "stream": True,
            "stream_options": {"include_usage": True},
        }
        if logprobs is not None:
            body["logprobs"] = logprobs
        return rec, asyncio.ensure_future(self._stream(rec, body))

    async def complete(self, prompt: str, max_tokens: int, **kw) -> Record:
        rec, task = self.launch(prompt, max_tokens, **kw)
        await task
        return rec

    async def _stream(self, rec: Record, body: dict) -> None:
        want_lp = "logprobs" in body
        rec.sent = time.monotonic()
        try:
            async with self._session.post(
                self.base_url + "/v1/completions", json=body
            ) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = f"HTTP {resp.status}: " \
                                f"{(await resp.text())[:200]}"
                    return
                async for raw in resp.content:
                    t = time.monotonic()
                    line = raw.strip()
                    if not line.startswith(b"data: "):
                        continue
                    payload = line[6:]
                    if payload == b"[DONE]":
                        rec.done = t
                        break
                    chunk = json.loads(payload)
                    if chunk.get("error"):
                        rec.error = str(chunk["error"])[:300]
                        return
                    if chunk.get("choices"):
                        rec.chunk_times.append(t)
                        if want_lp and rec.logprobs is None:
                            lp = chunk["choices"][0].get("logprobs")
                            if lp and lp.get("top_logprobs"):
                                rec.logprobs = lp["top_logprobs"][0]
                    if chunk.get("usage"):
                        rec.usage_prompt = chunk["usage"].get("prompt_tokens")
                        rec.usage_completion = chunk["usage"].get(
                            "completion_tokens")
        except asyncio.CancelledError:
            rec.error = "cancelled"
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:300]
