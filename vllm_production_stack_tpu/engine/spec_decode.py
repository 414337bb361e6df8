"""Speculative decoding proposers: n-gram prompt lookup and a draft model.

The reference stack's engines inherit vLLM's `--speculative-config
{"method": "ngram", ...}`: propose the next k tokens by matching the tail
of the sequence against its own history, then VERIFY all k in one model
step — the model's argmax at each proposed position either confirms the
proposal token or replaces it, so one dispatch yields 1..k+1 tokens
instead of 1. Greedy-only (verification of sampled tokens needs rejection
sampling; vLLM's ngram path is typically used the same way).

TPU shape of the idea: verification is exactly a chunked-prefill step with
argmax at EVERY position (models/llama.py:forward over the paged pool —
static (batch, k+1) shapes, no new kernel), and a row with no proposal
simply proposes nothing and gets its 1 bonus token — so the verify program
SUBSUMES plain decode for greedy rows and the scheduler can route all of
them through it.

`--speculative-config draft --draft-model <name>` (docs/36-speculative-
decoding.md) graduates past prompt lookup: DraftModelProposer runs a small
model from the models/ registry autoregressively for the k proposals. The
draft shares the target's paged KVBlockPool through a SCRATCH block-table
namespace (kv_cache.allocate_scratch): same allocator and byte budget, its
own device pages, and never content-addressed — a draft block can never
satisfy a prefix match, peer lookup, or KV export. N-gram stays the
zero-weight fallback for rows the draft declines (pool pressure).
"""

from __future__ import annotations

MAX_NGRAM = 4
# history window the proposer searches: bounds the per-step host cost at
# long context (this runs in the scheduler loop for every greedy row every
# decode step; vLLM's ngram speculator has the same knob)
MAX_LOOKBACK = 1024


def propose_ngram(
    tokens: list[int],
    k: int,
    min_ngram: int = 2,
    max_ngram: int = MAX_NGRAM,
    max_lookback: int = MAX_LOOKBACK,
) -> list[int] | None:
    """Propose up to k continuation tokens by matching the sequence's tail
    n-gram against its recent history (longest n first, most recent match
    wins). Returns None when no n-gram of length >= min_ngram recurs in the
    lookback window.

    When the continuation runs out of history before k tokens — the match
    sits right behind the tail, i.e. the sequence looks PERIODIC with the
    match-to-tail distance as its period — the proposal extrapolates by
    tiling that period. A cyclic decode (the workload n-gram speculation
    exists for) would otherwise cap every proposal at one period, no
    matter how large k is; a wrong extrapolation costs nothing beyond the
    normal verify rejection."""
    if k <= 0 or len(tokens) < min_ngram + 1:
        return None
    lo = max(0, len(tokens) - max_lookback)
    window = tokens[lo:]
    for n in range(min(max_ngram, len(window) - 1), min_ngram - 1, -1):
        tail = window[-n:]
        first = tail[0]
        # scan right-to-left over history (exclude the tail match itself)
        for start in range(len(window) - n - 1, -1, -1):
            # cheap first-element pre-check before the slice+compare
            if window[start] != first or window[start : start + n] != tail:
                continue
            cont = window[start + n : start + n + k]
            if not cont:
                continue
            if len(cont) < k:
                # periodic extrapolation: the hypothesis behind the match
                # is "the sequence repeats with period = match-to-end
                # distance" — keep tiling it past the history's edge
                period = len(window) - (start + n)
                base = window[start + n :]
                cont = [base[j % period] for j in range(k)]
            return cont
    return None


class _DraftState:
    """Per-request draft-model KV state: the scratch blocks holding the
    draft's paged KV for this request, and how many leading positions hold
    KV of TRUE (accepted) tokens. Positions at or beyond `valid` may hold
    stale speculative writes; the next catch-up feed overwrites them in
    place (slot = position via the block table), and attention never reads
    past the fed context length."""

    __slots__ = ("block_table", "valid", "shadow")

    def __init__(self, shadow):
        self.block_table: list[int] = []
        self.valid = 0
        self.shadow = shadow  # runner-facing Request double


class DraftModelProposer:
    """Autoregressive draft-model proposer sharing the target's paged pool.

    One small ModelRunner (the draft) proposes k tokens per eligible row:
    a batched catch-up feed pushes every accepted-but-unfed token through
    the draft (a prefill-shaped dispatch, sampling the first draft token at
    the tail), then ONE fused decode window of k-1 steps drafts the rest —
    two draft dispatches per proposal round for the whole batch, padded
    through the runner's existing bucket ladder + pad-up program cache so
    draft-batch shapes never retrigger compilation on the hot path.

    Pool discipline: scratch blocks come from the shared KVBlockPool
    (allocate_scratch — never registered, never matchable) and are refused
    rather than fought over: a row whose allocation would squeeze the pool
    below `min_free_reserve` skips drafting this round (the scheduler falls
    back to n-gram), so the draft can never preempt target requests."""

    name = "draft"

    def __init__(
        self, runner, pool, max_model_len: int, min_free_reserve: int = 8
    ):
        self.runner = runner  # the DRAFT ModelRunner
        self.pool = pool  # the SHARED KVBlockPool
        self.block_size = pool.block_size
        self.max_model_len = max_model_len
        self.min_free_reserve = min_free_reserve
        self._states: dict[str, _DraftState] = {}
        # observability: rows that fell back to n-gram on pool pressure
        # (surfaced on /debug/timing's spec section)
        self.declined_rows = 0
        # proposal memo: the scheduler's verify/decode alternation can
        # discard a whole propose_batch after the draft already ran (the
        # plain group won the turn) — the next schedule() re-asks with the
        # request state unchanged, so the answer is reusable. Keyed on
        # (true length, spec tail): a request's true sequence is
        # append-only, so equal length + equal tail == equal sequence.
        # Dropped with the state on release().
        self._memo: dict[str, tuple[tuple, list[int]]] = {}

    def _state(self, req) -> _DraftState:
        st = self._states.get(req.request_id)
        if st is None:
            from .request import Request, SamplingParams

            shadow = Request(
                request_id=f"draft:{req.request_id}",
                prompt_token_ids=[],
                sampling=SamplingParams(
                    max_tokens=1 << 30, temperature=0.0, ignore_eos=True
                ),
            )
            st = _DraftState(shadow)
            self._states[req.request_id] = st
        return st

    def release(self, request_id: str) -> None:
        """Free a request's draft scratch blocks (finish/preempt/abort)."""
        self._memo.pop(request_id, None)
        st = self._states.pop(request_id, None)
        if st is not None:
            for blk in reversed(st.block_table):
                self.pool.free_scratch(blk)

    def propose_batch(
        self, reqs: list, k: int, spec_tails: dict | None = None
    ) -> dict[str, list[int]]:
        """Draft up to k tokens for each request. Rows the draft declines
        (pool pressure, position past max_model_len) are absent from the
        returned map — the scheduler's n-gram fallback covers them.
        Deterministic per (request sequence): greedy drafting, so the
        serial and pipelined loops see identical proposals.

        `spec_tails[rid]` (pipelined verify-on-verify, docs/36) appends a
        row's in-flight verify proposals to its sequence: the draft feeds
        through them — host-known values under the full-acceptance
        speculation — and the returned proposal DROPS its first drafted
        token, whose position the in-flight bonus token (device-chained by
        the runner) covers. The tail's KV is speculative, so `valid` stays
        at the TRUE length and the next catch-up overwrites it in place."""
        if k <= 0 or not reqs:
            return {}
        from .scheduler import DecodeWork, PrefillWork

        spec_tails = spec_tails or {}
        bs = self.block_size
        rows: list[tuple] = []  # (req, st, seq, true_len)
        memo_hits: dict[str, list[int]] = {}
        for req in reqs:
            true_seq = req.all_token_ids
            tail = tuple(spec_tails.get(req.request_id, ()))
            seq = true_seq + list(tail)
            # the draft writes KV for positions < len(seq) + k and the
            # verify feed itself must stay inside the model length
            if len(seq) + k >= self.max_model_len:
                continue
            memo = self._memo.get(req.request_id)
            if memo is not None and memo[0] == (len(true_seq), tail, k):
                # the alternation discarded this exact proposal last
                # schedule() — reuse it, no draft dispatch
                memo_hits[req.request_id] = list(memo[1])
                continue
            st = self._state(req)
            need = -(-(len(seq) + k) // bs)
            grow = need - len(st.block_table)
            if grow > 0:
                if self.pool.num_free - grow < self.min_free_reserve:
                    self.declined_rows += 1
                    continue
                ok = True
                while len(st.block_table) < need:
                    blk = self.pool.allocate_scratch()
                    if blk is None:
                        ok = False
                        break
                    st.block_table.append(blk)
                if not ok:
                    self.declined_rows += 1
                    continue  # keep what we got; next round may free up
            st.shadow.block_table = st.block_table
            rows.append((req, st, seq, len(true_seq)))
        if not rows:
            return memo_hits
        # -- batched catch-up: feed every not-yet-valid true token ---------
        # (first proposal: the whole prompt; steady state: the tokens the
        # last verify accepted). Each dispatch holds what the draft's
        # largest prefill program can compute (`prefill_row_room`); only
        # the FINAL chunk of a row samples (its tail logits are the first
        # draft token).
        first: dict[str, int] = {}
        # every row re-feeds at least its current tail token (a re-propose
        # after a dropped verify row has nothing new to feed, but still
        # needs the tail logits sampled; rewriting one position's KV with
        # the same token is a no-op)
        pending = {
            id(st): min(st.valid, len(seq) - 1) for _, st, seq, _ in rows
        }
        while True:
            work = PrefillWork()
            for req, st, seq, _ in rows:
                start = pending[id(st)]
                if start >= len(seq):
                    continue
                # what this dispatch can still take beside the rows it
                # holds (they are outside the scheduler's token budget:
                # the runner's largest prefill program is the bound)
                room = self.runner.prefill_row_room(
                    [len(t) for t in work.token_ids]
                )
                if room <= 0:
                    break  # full: the rest catch up in the next dispatch
                end = min(len(seq), start + room)
                idxs = range(start, end)
                work.add_row(
                    request=st.shadow,
                    token_ids=[seq[i] for i in idxs],
                    positions=list(idxs),
                    slot_mapping=[],
                    context_len=end,
                    sample=end == len(seq),
                )
                pending[id(st)] = end
            if not work.requests:
                break
            sampled = self.runner.execute(work)
            for i, shadow in enumerate(work.requests):
                if work.sample[i]:
                    rid = shadow.request_id[len("draft:"):]
                    first[rid] = int(sampled[i][0])
        full: dict[str, list[int]] = {}
        for req, st, seq, true_len in rows:
            # spec-tail positions (>= true_len) hold unconfirmed KV — the
            # next round's catch-up re-feeds them with whatever the verify
            # actually accepted, overwriting in place
            st.valid = true_len
            full[req.request_id] = [first[req.request_id]]
        # -- one fused decode window drafts the remaining tokens -----------
        # (window k so tailed rows still return k proposals after dropping
        # their first draft — untailed rows just ignore the extra token)
        dec = DecodeWork(
            requests=[st.shadow for _, st, _, _ in rows],
            window=k,
            token_ids=[full[r.request_id][0] for r, _, _, _ in rows],
            positions=[len(seq) for _, _, seq, _ in rows],
        )
        tail = self.runner.execute(dec)
        for i, (req, _, _, _) in enumerate(rows):
            full[req.request_id].extend(int(t) for t in tail[i])
        out: dict[str, list[int]] = dict(memo_hits)
        for req, _, _, true_len in rows:
            rid = req.request_id
            drafted = full[rid]
            # tailed rows: drafted[0] predicts the in-flight bonus position
            # (covered by the device-chained first fed token) — drop it
            p = drafted[1 : k + 1] if rid in spec_tails else drafted[:k]
            out[rid] = p
            self._memo[rid] = (
                (true_len, tuple(spec_tails.get(rid, ())), k), list(p)
            )
        return out
