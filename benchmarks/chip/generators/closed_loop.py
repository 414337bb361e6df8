"""Closed loop: `clients` callers, each sending its next request when its
last one ends. No shared prefix: every prompt is unique from its first
character on. The window is held at capacity, so its judge is tokens/s.

Parameters (traffic file): clients, prompt_len and output_len
(distributions), multiset (size, a multiple of 8), ramp_s,
min_first_output. Every seed sends the same sequence of lengths and changes
only the text (and, in the engine, the weights): with the seed shuffling
inside blocks of 8, runs of other seeds differed by 2.4% where two runs of
one seed agreed to 0.07% (my chip runs, PR 23) -- which requests straddle the
window's edges moved the token count.
"""

from __future__ import annotations

import asyncio
import random

from client import text_of
from traffic import decorrelate, int_quantiles, spread_order


class Generator:
    def __init__(self, params: dict, seed: int):
        self.p = params
        n = params["multiset"]
        prompts = int_quantiles(params["prompt_len"], n)
        outputs = int_quantiles(params["output_len"], n)
        pair = decorrelate(n)
        pairs = [(prompts[i], outputs[pair[i]]) for i in range(n)]
        self._rng = random.Random(seed)
        self.sequence = [pairs[i] for i in spread_order(n)]
        self._next = 0

    def multiset(self) -> dict:
        """What the seed may not change (tests compare this across seeds)."""
        return {"pairs": sorted(self.sequence), "gaps": []}

    def order(self) -> list:
        return list(self.sequence)

    def _take(self) -> tuple[int, int, int]:
        k = self._next
        self._next += 1
        p, o = self.sequence[k % len(self.sequence)]
        return k, p, o

    async def run(self, client, window) -> None:
        """Ramp (out of phase), open the window, keep the loop closed until
        every request that overlaps the window has ended."""
        n_clients = self.p["clients"]
        mine: list = []
        started = [asyncio.Event() for _ in range(n_clients)]

        async def one_client(i: int) -> None:
            first = True
            while True:
                k, p_len, o_len = self._take()
                if first:
                    # start out of phase: client i's first answer is cut to
                    # (i+1)/clients of its length, so that ends are spread
                    # from the start instead of arriving together. Never
                    # under two fused decode windows: a lone first row with
                    # a few tokens left would ask for a 1/2/4-step window
                    # program that no steady state of this mix uses
                    o_len = max(self.p.get("min_first_output", 16),
                                round(o_len * (i + 1) / n_clients))
                prompt = f"{k:08d} " + text_of(p_len - 9, self._rng)
                rec, task = client.launch(
                    prompt, o_len, kind="ramp" if first else "chat")
                mine.append(rec)
                if first:
                    while not task.done() and not rec.chunk_times:
                        await asyncio.sleep(0.01)
                    started[i].set()
                    first = False
                await task

        tasks = [asyncio.ensure_future(one_client(i))
                 for i in range(n_clients)]
        try:
            await asyncio.gather(*(e.wait() for e in started))
            await asyncio.sleep(self.p["ramp_s"])
            await window.open()
            await window.wait_closed()
            # full load until the requests that overlap the window have ended
            while any(window.overlaps(r) and r.done is None
                      and r.error is None for r in mine):
                await asyncio.sleep(0.05)
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        for r in mine:
            r.counted = window.overlaps(r) and r.error != "cancelled"
