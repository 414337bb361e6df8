"""HBM KV page pool: allocator + content-addressed prefix cache.

The reference delegates paged-KV management to vLLM and exposes only its
metrics (`vllm:gpu_cache_usage_perc`, `vllm:gpu_prefix_cache_*` — scraped by
the router, src/vllm_router/stats/engine_stats.py:63-76). This module is the
TPU engine's equivalent: host-side bookkeeping for the device-side paged pool
(the actual pages live in one stacked jax.Array, models/llama.py
init_kv_cache). Block 0 is the reserved null page (ops/attention.py).

Prefix caching is content-addressed like vLLM's: a *full* block's identity is
the rolling hash of (parent block hash, its tokens). Blocks whose refcount
drops to zero are not returned to the free list immediately — they park in an
LRU of evictable cached blocks, so a new request with a shared prefix can
re-reference their KV without recompute. The hit/query counters back the
`prefix_cache_hit_rate` metric contract.
"""

from __future__ import annotations

import hashlib
import logging
from collections import OrderedDict, deque
from dataclasses import dataclass

import numpy as np

from .kv_codec import decode_block, logical_shape

logger = logging.getLogger(__name__)

_ROOT_HASH = 0x9E3779B97F4A7C15


def chain_hash(parent: int, tokens: tuple[int, ...]) -> int:
    """Collision-resistant rolling block hash. Python's hash() would make
    wrong-KV collisions constructible (even adversarially, in a multi-tenant
    server); a truncated sha256 over parent||tokens removes that."""
    h = hashlib.sha256(int(parent).to_bytes(16, "little", signed=False))
    h.update(b"".join(int(t).to_bytes(8, "little", signed=True) for t in tokens))
    return int.from_bytes(h.digest()[:16], "little")


def chain_hash_run(
    parent: int, token_ids: list[int], block_size: int
) -> list[int]:
    """Chain hash of every FULL block of `token_ids`, in order, rooted at
    `parent` — the single definition of block identity. The pool's `_chain`
    and the cluster KV index (kv_index.py) both delegate here, so an indexed
    /lookup can never diverge from what a real match would reuse. Uses the
    native batch hasher (csrc/kvhash.cpp via utils/native.py) when available,
    one Python sha256 round-trip per block otherwise."""
    from ..utils.native import chain_hashes_native

    hashes = chain_hashes_native(parent, token_ids, block_size)
    if hashes is not None:
        return hashes
    out: list[int] = []
    for i in range(len(token_ids) // block_size):
        parent = chain_hash(
            parent, tuple(token_ids[i * block_size : (i + 1) * block_size])
        )
        out.append(parent)
    return out


@dataclass
class CacheStats:
    queries: int = 0  # full prompt blocks looked up
    hits: int = 0  # full prompt blocks served from cache

    @property
    def hit_rate(self) -> float:
        return self.hits / self.queries if self.queries else 0.0


class WindowPageGroup:
    """Host-side accounting for the WINDOW layers' pages — the second of an
    engine's two page groups when its model mixes window and full attention
    layers (models/llama.py:init_kv_cache gives each kind arrays of its own
    size). `KVBlockPool` is the full group: a row's whole context, content
    addressed. This group holds what a window layer can still read: a row's
    table here has the full table's logical indexing, and a page wholly
    behind the window of the row's RESOLVED position is given back — its
    table slot becomes 0, the null page, which no kernel reads: a window
    layer's walk starts at the page that holds the first visible key
    (ops/paged_attention_pallas.py) and the XLA path masks it.

    No content addressing: a prefix hit would also need the window group's
    pages for the window before its boundary, which were given back — so
    the group exists only where prefix caching is off, and with it on the
    window layers hold the whole context in the full group like any other
    layer (engine/memory.py:resolve_window_blocks; LLMEngine says at start
    which). Page 0 is the null page, as in the full group."""

    def __init__(self, num_blocks: int, block_size: int, window: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the null page)")
        self.num_blocks, self.block_size, self.window = (
            num_blocks, block_size, window)
        self._free: deque[int] = deque(range(1, num_blocks))
        self.given_back_total = 0  # pages returned BEHIND a live row

    @property
    def num_usable(self) -> int:
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_resident(self) -> int:
        return self.num_usable - len(self._free)

    @property
    def usage_perc(self) -> float:
        return 1.0 - self.num_free / self.num_usable

    def allocate(self) -> int | None:
        return self._free.popleft() if self._free else None

    def free_block(self, blk: int) -> None:
        self._free.append(blk)

    def first_needed_block(self, num_computed_tokens: int) -> int:
        """The first logical block a row still needs: the one that holds
        the first key its lowest unresolved query (position
        `num_computed_tokens`) sees."""
        return max(0, num_computed_tokens - self.window + 1) // self.block_size

    def give_back(self, table: list[int], first_live: int,
                  num_computed_tokens: int) -> int:
        """Return the pages of `table` (logical index -> page, 0 = given
        back) that lie wholly behind the window of a row whose positions
        below `num_computed_tokens` are resolved; `first_live` is the first
        index that may still hold a page. Returns the new `first_live`."""
        upto = min(self.first_needed_block(num_computed_tokens), len(table))
        for j in range(first_live, upto):
            if table[j]:
                self.free_block(table[j])
                table[j] = 0
                self.given_back_total += 1
        return max(first_live, upto)

    def release(self, table: list[int], first_live: int = 0) -> None:
        """Free every page a row still holds (finish, preemption)."""
        for j in range(first_live, len(table)):
            if table[j]:
                self.free_block(table[j])
        table.clear()


class StateSlots:
    """Host-side accounting for the STATE SLOTS of a model with layers that
    hold a recurrent state (LayerSpec.holds_state) — without pages (a gated
    delta-rule layer) or BESIDE them (state-space heads next to attention
    heads); models/llama.py:init_kv_cache gives each such layer {"s",
    "conv"} pools of two banks x `num_slots` slots, of the geometry of the
    layer's recurrence (ModelConfig.state_geometry: this class counts
    slots and knows no shape). The second
    kind of cache beside the pages, in the same manager: a row takes ONE
    slot with its first pages and gives it back with them — when it ends, is
    aborted or is preempted — and holds it whatever its context length.
    Slot 0 is the null slot, which padding rows read and write. One slot a
    seat, so slots cannot run out before seats do.

    No slot is ever cleared on the host or trusted on the device: the
    program that runs a row's FIRST token starts it from zeros
    (models/llama.py:forward). No content addressing: a prefix hit would
    need the state at its boundary, which nothing keeps, so the engine
    runs without prefix caching beside such layers (and says so at start).

    Two banks (bank b's slot s lies at b * num_slots + s of the pools): a
    dispatch reads a row's state in the row's bank and writes it in the
    other (ModelRunner._state_slot_arrays), and the row's bank flips when the
    dispatch goes out (Scheduler.begin_speculative). A dispatch that is
    rolled back (Scheduler.rollback_speculative) has run on the device all
    the same — unlike a page, a state overwritten in place could not be
    computed again — but what it read is untouched: the row's bank flips
    back and the re-dispatch starts from it."""

    def __init__(self, num_slots: int):
        if num_slots < 2:
            raise ValueError("need at least 2 slots (slot 0 is the null slot)")
        self.num_slots = num_slots
        self._free: deque[int] = deque(range(1, num_slots))

    @property
    def num_usable(self) -> int:
        return self.num_slots - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_in_use(self) -> int:
        return self.num_usable - len(self._free)

    def allocate(self) -> int | None:
        return self._free.popleft() if self._free else None

    def release(self, slot: int) -> None:
        if slot:
            self._free.append(slot)


class KVBlockPool:
    """Host-side accounting for the device page pool of ONE engine."""

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        host_tier=None,
    ):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the null page)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        # optional HostKVTier: evicted cached blocks offload HBM→host and
        # prefix matches continue into it (engine/kv_host_tier.py)
        self.host_tier = host_tier
        # the window layers' page group, where the model has window layers
        # (the engine attaches it; the scheduler grows and gives back both)
        self.window: WindowPageGroup | None = None
        # the state slots of a model with layers that hold a recurrent
        # state (the scheduler attaches them): the other KIND of cache
        self.state: StateSlots | None = None
        # event stream for the cluster KV index (engine/kv_events.py): every
        # transition of a hash's local matchability is published so the KV
        # controller/router can answer /lookup without probing this engine.
        # Always on with prefix caching — emission is a couple of deque ops
        # per NEW full block, nothing consumes it until a publisher attaches.
        self.events = None
        if enable_prefix_caching:
            from .kv_events import KVEventLog

            self.events = KVEventLog()
        if host_tier is not None:
            # ring and disk drops unpublish hashes that are no longer
            # matchable anywhere local (see _on_host_drop)
            host_tier.on_drop = self._on_host_drop
            if getattr(host_tier, "disk", None) is not None:
                host_tier.disk.on_drop = self._on_host_drop
            # migration-aware ring eviction shares the pool's replica set
            # (bound method of the set object — survives in-place updates)
            host_tier.is_replicated = (
                lambda h: h in self._replicated
            )
        # page geometry remote fetches are validated against; the engine
        # sets this once the runner's pool exists (None = skip validation,
        # e.g. unit tests with no device pool)
        self.expected_block_shape: tuple[int, ...] | None = None
        # block 0 reserved as the null page
        self._free: deque[int] = deque(range(1, num_blocks))
        self._ref: dict[int, int] = {}
        # content-addressing maps (full, computed blocks only)
        self._hash_to_block: dict[int, int] = {}
        self._block_to_hash: dict[int, int] = {}
        # refcount-0 cached blocks, LRU order (oldest first -> evicted first)
        self._evictable: OrderedDict[int, None] = OrderedDict()
        # migration-aware eviction (docs/39-device-peer-kv.md, ROADMAP 2b):
        # hashes the cluster's replication controller confirmed resident on
        # ≥1 PEER engine. Eviction prefers these — losing a replicated
        # block costs a peer pull, losing the cluster's last copy costs a
        # recompute. Advisory only: a stale entry just mis-orders victims.
        self._replicated: set[int] = set()
        self.stats = CacheStats()
        # per-block KV origin of the LAST match_prefix call ("hbm" | "host"
        # | "disk" | "remote", parallel to its return) — consumed by the
        # scheduler's hydration attribution before the next match runs
        self.last_match_sources: list[str] = []
        # blocks currently held by the scratch (non-content-addressed)
        # namespace — the draft proposer's pool share, for observability
        self.scratch_blocks = 0
        if enable_prefix_caching:
            # warm the native batch hasher NOW (pool construction = engine
            # init, where XLA compiles already dominate) — never lazily from
            # the admission path, where a cold g++ build would stall the
            # first request and everything queued behind it
            from ..utils.native import chain_hashes_native

            chain_hashes_native(_ROOT_HASH, [0] * block_size, block_size)

    # -- capacity ----------------------------------------------------------

    @property
    def num_usable(self) -> int:
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        """Blocks allocatable right now (free list + evictable cached)."""
        return len(self._free) + len(self._evictable)

    @property
    def usage_perc(self) -> float:
        """Fraction of pool actively referenced — the TPU analogue of
        vllm:gpu_cache_usage_perc."""
        return 1.0 - self.num_free / self.num_usable

    @property
    def group_usage(self) -> dict[str, float]:
        """Share of each page group in use. ONE group ("full") without
        window layers."""
        out = {"full": self.usage_perc}
        if self.window is not None:
            out["window"] = self.window.usage_perc
        return out

    @property
    def fullest_usage_perc(self) -> float:
        """The fuller group's share — the one that would preempt: what
        `tpu:hbm_kv_usage_perc` reports."""
        return max(self.group_usage.values())

    @property
    def growable_blocks(self) -> int:
        """How many more logical blocks a row's tables could grow by right
        now: both groups grow in lockstep, so the scarcer one decides."""
        if self.window is None:
            return self.num_free
        return min(self.num_free, self.window.num_free)

    # -- allocation --------------------------------------------------------

    def allocate_scratch(self) -> int | None:
        """Allocate a block OUTSIDE the content-addressed namespace — the
        draft-model proposer's block-table rung (docs/36-speculative-
        decoding.md). Scratch blocks share the allocator and byte budget
        but are never registered: no hash chain ever points at one, so a
        draft page can never satisfy a prefix match, a /kv/lookup probe, a
        peer /kv/peer_contains walk, or a KV export — isolation is
        structural, not filtered. Freed via free_scratch."""
        blk = self.allocate()
        if blk is not None:
            self.scratch_blocks += 1
        return blk

    def free_scratch(self, blk: int) -> None:
        self.scratch_blocks -= 1
        self.free_block(blk)

    # oldest-end window scanned for a peer-replicated victim before the
    # plain LRU-oldest falls: bounds the preference at O(32) dict probes
    # per eviction, preserving allocate()'s hot-path cost
    _VICTIM_SCAN = 32

    def mark_replicated(self, hashes: list[int]) -> int:
        """Record that `hashes` are resident on ≥1 peer engine (the
        replication controller confirmed a copy landed — docs/39), making
        their blocks PREFERRED eviction victims here: the cluster keeps
        the copy either way, so this engine should shed them first and
        keep blocks whose only copy it holds. Returns how many are
        currently block-resident (diagnostic)."""
        if len(self._replicated) > 4 * self.num_blocks:
            # advisory set, bounded: replica hints outliving the pool many
            # times over carry no ordering signal worth the memory
            self._replicated.clear()
        self._replicated.update(hashes)
        return sum(1 for h in hashes if h in self._hash_to_block)

    def _pick_victim(self) -> int:
        """Next eviction victim: a peer-replicated block from the oldest
        _VICTIM_SCAN evictable entries when one exists, else LRU-oldest
        (migration-aware eviction, docs/39-device-peer-kv.md)."""
        if self._replicated:
            for i, b in enumerate(self._evictable):
                if i >= self._VICTIM_SCAN:
                    break
                if self._block_to_hash.get(b) in self._replicated:
                    del self._evictable[b]
                    return b
        blk, _ = self._evictable.popitem(last=False)
        return blk

    def allocate(self) -> int | None:
        if self._free:
            blk = self._free.popleft()
        elif self._evictable:
            blk = self._pick_victim()
            h = self._block_to_hash.pop(blk)
            self._hash_to_block.pop(h, None)
            if self.host_tier is not None:
                # offload BEFORE the block id is handed out for reuse — the
                # device executes in dispatch order, so the host copy reads
                # the old pages even though the fetch is asynchronous
                self.host_tier.store(h, blk)
            elif self.events is not None:
                # no host tier: the hash stopped being matchable here (with
                # a host tier it stays matchable in the ring; the ring's own
                # drop hook emits the evict when it truly leaves)
                self.events.emit_evict(h)
        else:
            return None
        self._ref[blk] = 1
        return blk

    def free_block(self, blk: int) -> None:
        ref = self._ref.get(blk)
        if ref is None:
            raise KeyError(f"double free of block {blk}")
        if ref > 1:
            self._ref[blk] = ref - 1
            return
        del self._ref[blk]
        if blk in self._block_to_hash:
            self._evictable[blk] = None  # parked, content still addressable
        else:
            self._free.append(blk)

    # -- prefix caching ----------------------------------------------------

    def _chain(self, token_ids: list[int], parent: int):
        """Chain hash of each FULL block of the prompt, in order — shared by
        match_prefix and match_length via module-level `chain_hash_run`."""
        return chain_hash_run(parent, token_ids, self.block_size)

    def match_prefix(
        self, token_ids: list[int], parent: int | None = None,
        limit_blocks: int | None = None,
    ) -> list[int]:
        """Longest run of cached full blocks matching the prompt's head —
        HBM-resident blocks first, then continuing into the host tier (each
        host hit uploads into a freshly allocated HBM block). Acquires a
        reference on every matched block. `parent` is the chain root — the
        scheduler salts it per LoRA adapter so base and adapter KV never
        cross-match (their K/V bytes differ when k/v projections carry
        deltas).

        `limit_blocks` caps the match (hydration planner admissions: the
        scheduler consumes the leading HBM/host-ring run synchronously and
        plans the disk/remote remainder as async chunk loads instead of
        blocking here — docs/31-hydration-planner.md).

        Hydration attribution (docs/30-kv-flow-telemetry.md): alongside
        the matched blocks, `last_match_sources` records where each came
        from — "hbm" | "host" | "disk" | "remote", parallel to the return
        value — so the scheduler can classify the request's prompt tokens
        by KV origin exactly once at admission."""
        matched: list[int] = []
        self.last_match_sources = sources = []
        if not self.enable_prefix_caching:
            return matched
        if limit_blocks is not None:
            # hash only what the cap can match — the planner admission
            # already chained the full prompt once in probe_prefix
            token_ids = token_ids[: limit_blocks * self.block_size]
        hashes = list(
            self._chain(token_ids, _ROOT_HASH if parent is None else parent)
        )
        for idx, h in enumerate(hashes):
            self.stats.queries += 1
            blk = self._hash_to_block.get(h)
            if blk is None:
                blk, source = self._reload_from_host(h)
                if blk is None:
                    if limit_blocks is not None:
                        break  # planner admission: never block on remote
                    # both local tiers miss: continue the chain into the
                    # remote store (one batched mget for the remainder)
                    remote = self._match_remote(hashes[idx:])
                    matched.extend(remote)
                    sources.extend(["remote"] * len(remote))
                    break
            else:
                self._acquire(blk)
                source = "hbm"
            self.stats.hits += 1
            matched.append(blk)
            sources.append(source)
        return matched

    def _match_remote(self, hashes: list[int]) -> list[int]:
        """Fetch the consecutive remote-held prefix of `hashes` into freshly
        allocated HBM blocks (cross-engine KV reuse — the LMCache-server
        capability). Fetched blocks are promoted into the host ring so the
        next match stays local. queries for hashes[0] was already counted by
        the caller; the rest count here.

        Mirrors import_blocks: geometry is validated against the engine's
        page shape (a version-skewed remote store degrades to a miss, never
        a corrupt match), and the hash→block mappings + hit counts commit
        only AFTER the batched device upload succeeds — a failed upload frees
        the staged blocks instead of leaving hashes pointing at pages whose
        KV was never written."""
        remote = getattr(self.host_tier, "remote", None)
        if remote is None:
            return []
        want = self.expected_block_shape
        staged: list = []  # (hash, blk, data) for ONE batched device upload
        for i, (h, data) in enumerate(zip(hashes, remote.fetch_run(hashes))):
            if i > 0:
                self.stats.queries += 1
            # geometry check on the LOGICAL shape: at-rest-encoded fetches
            # arrive as EncodedKVBlock (wire form) and carry their decoded
            # shape in metadata
            if want is not None and logical_shape(data) != tuple(want):
                logger.warning(
                    "remote KV block %x has shape %s, engine needs %s — "
                    "dropping the fetched run (version-skewed store?)",
                    h, logical_shape(data), want,
                )
                break
            blk = self.allocate()  # may evict (offload+write-through) others
            if blk is None:
                break
            staged.append((h, blk, data))
        if not staged:
            return []
        try:
            # one dispatch for the whole fetched run — per-block uploads
            # cost a device dispatch each. THIS is the
            # dequant-on-adopt boundary: encoded blocks decode here, right
            # before the device upload.
            self.host_tier.upload_many(
                [blk for _, blk, _ in staged],
                [decode_block(d) for _, _, d in staged],
            )
        except Exception:
            logger.exception(
                "remote KV upload failed — freeing %d staged blocks and "
                "degrading to a cache miss", len(staged)
            )
            for _, blk, _ in staged:
                self.free_block(blk)
            return []
        matched: list[int] = []
        for h, blk, data in staged:
            self._hash_to_block[h] = blk
            self._block_to_hash[blk] = h
            self.host_tier.insert_resolved(h, data)
            if self.events is not None:
                self.events.emit_admit(h, 0)  # parent unknown mid-chain
            self.stats.hits += 1
            matched.append(blk)
        return matched

    # -- hydration planner support (docs/31-hydration-planner.md) ----------

    def probe_prefix(
        self, token_ids: list[int], parent: int | None = None,
        local_only: bool = False, peer=None, owner_hint: str | None = None,
    ) -> tuple[list[int], list[str], str]:
        """(hashes, tiers, peer_owner) of the longest consecutively-
        resident run of full prompt blocks across EVERY tier, WITHOUT
        moving data, taking references, or touching the hit counters —
        the residency map the compute-or-load planner decides over.
        tiers[i] is "hbm" | "host" | "disk" | "remote" | "peer" |
        "device" (a peer continuation on a shared-mesh owner); the
        remote continuation is one batched contains round trip (no
        payload), same as match_length. `local_only` skips every round
        trip — the `off` kill switch must not keep a sick remote store
        (or peer) on the admission path.

        Peer continuation (docs/35-peer-kv-reuse.md): when the local +
        remote run ends short of the full chain and a `peer` client
        (engine/kv_peer.PeerKVTier) is supplied, the probe continues into
        ANOTHER ENGINE's tiers — the router's `owner_hint` names the
        owner directly (priced route-vs-migrate stamped it upstream), else
        one cluster-index lookup rediscovers it; either way one
        /kv/peer_contains round trip confirms the owner's ACTUAL
        consecutive residency (the index can be seconds stale).
        peer_owner is the confirmed owner URL, "" when the run has no
        peer tail."""
        if not self.enable_prefix_caching:
            return [], [], ""
        hashes = list(
            self._chain(token_ids, _ROOT_HASH if parent is None else parent)
        )
        tiers: list[str] = []
        for idx, h in enumerate(hashes):
            if h in self._hash_to_block:
                tiers.append("hbm")
                continue
            loc = (
                self.host_tier.location(h)
                if self.host_tier is not None
                else ""
            )
            if loc:
                tiers.append(loc)
                continue
            if not local_only:
                remote = getattr(self.host_tier, "remote", None)
                if remote is not None:
                    n = remote.contains_run(hashes[idx:])
                    tiers.extend(["remote"] * n)
            break
        peer_owner = ""
        start = len(tiers)
        # the cluster lookup is a synchronous round trip on the step
        # thread: only rediscover when the non-resident remainder is big
        # enough that a peer pull could plausibly beat recomputing it —
        # tiny tails aren't worth an admission-path hop (an explicit
        # router hint is trusted regardless: its round trip was already
        # paid at the router)
        MIN_LOOKUP_BLOCKS = 4
        if peer is not None and not local_only and start < len(hashes):
            owner = (owner_hint or "").rstrip("/")
            if not owner and len(hashes) - start >= MIN_LOOKUP_BLOCKS:
                owner, matched = peer.cluster_lookup(hashes, self.block_size)
                # the index answers from the chain ROOT: an owner whose
                # whole run is shorter than what this engine already has
                # locally adds nothing beyond `start`
                if owner and matched <= start:
                    owner = ""
            if owner:
                n = peer.contains_run(owner, hashes[start:])
                if n > 0:
                    # "device" when the owner negotiated the device-path
                    # transport (shared mesh — docs/39-device-peer-kv.md):
                    # same peer continuation, collective-priced tier label
                    tf = getattr(peer, "transport_for", None)
                    tiers.extend([tf(owner) if tf else "peer"] * n)
                    peer_owner = owner
        return hashes[: len(tiers)], tiers, peer_owner

    def adopt_planned_run(
        self, hashes: list[int], arrays: list
    ) -> list[int] | None:
        """Commit one LANDED hydration chunk: upload its fetched host-RAM
        bytes into freshly allocated HBM blocks and register them, taking
        a reference on every block for the adopting request (allocate()
        hands blocks out at refcount 1; a block that raced back into HBM
        is re-acquired instead of re-uploaded — its arrays slot may be
        None). All-or-nothing: any allocation/geometry/upload failure
        frees everything staged and returns None, and the scheduler falls
        back to recomputing the chunk. Same commit discipline as
        _match_remote: hash→block mappings land only AFTER the batched
        device upload succeeds."""
        want = self.expected_block_shape
        staged: list[tuple[int, int, object]] = []  # (hash, blk, data|None)
        for h, data in zip(hashes, arrays):
            existing = self._hash_to_block.get(h)
            if existing is not None:
                self._acquire(existing)
                staged.append((h, existing, None))
                continue
            if data is None or (
                want is not None
                and logical_shape(data) != tuple(want)
            ):
                # missing bytes (evicted hbm-tier block) or a version-
                # skewed remote payload: the chunk cannot adopt
                for _, blk, _ in staged:
                    self.free_block(blk)
                return None
            blk = self.allocate()
            if blk is None:
                for _, bl, _ in staged:
                    self.free_block(bl)
                return None
            staged.append((h, blk, data))
        uploads = [(blk, d) for _, blk, d in staged if d is not None]
        if uploads:
            try:
                # dequant-on-adopt: hydration chunks fetched from remote/
                # peer tiers land in wire form and decode only here, at
                # the device-upload boundary
                self.host_tier.upload_many(
                    [blk for blk, _ in uploads],
                    [decode_block(d) for _, d in uploads],
                )
            except Exception:
                logger.exception(
                    "hydration chunk upload failed — freeing %d staged "
                    "blocks and falling back to recompute", len(staged)
                )
                for _, blk, _ in staged:
                    self.free_block(blk)
                return None
        for h, blk, data in staged:
            if data is not None:
                self._hash_to_block[h] = blk
                self._block_to_hash[blk] = h
                # promote into the ring so the next match (and a
                # preempted resume) stays local
                self.host_tier.insert_resolved(h, data)
                if self.events is not None:
                    self.events.emit_admit(h, 0)  # parent unknown mid-chain
            self.stats.hits += 1
        return [blk for _, blk, _ in staged]

    # -- adoption staging (KV transfer, both transports) -------------------

    def stage_adoption(self, hashes: list[int]):
        """Allocate destination blocks for the non-resident members of a
        shipped hash run. Returns (staged, pinned): staged = [(hash, blk)]
        to fill and commit; pinned = already-resident blocks REF-PINNED for
        the duration — without the pin, a later allocate() in this same
        staging could evict a resident chain member, leaving the freshly
        adopted blocks unreachable behind a chain hole. Call exactly one of
        commit_adoption/abort_adoption afterwards. The ONE definition of
        adoption bookkeeping shared by the host-staged HTTP path
        (kv_transfer.import_blocks) and the device path
        (kv_device_transfer.ship_kv_device)."""
        staged: list[tuple[int, int]] = []
        pinned: list[int] = []
        for h in hashes:
            existing = self._hash_to_block.get(h)
            if existing is not None:
                self._acquire(existing)
                pinned.append(existing)
                continue
            blk = self.allocate()
            if blk is None:
                break
            staged.append((h, blk))
        return staged, pinned

    def commit_adoption(
        self, staged: list[tuple[int, int]], pinned: list[int]
    ) -> None:
        """Register filled blocks as content-addressable evictable cache."""
        for h, blk in staged:
            self._hash_to_block[h] = blk
            self._block_to_hash[blk] = h
            if self.events is not None:
                self.events.emit_admit(h, 0)  # parent unknown (adopted run)
            self.free_block(blk)  # park: refcount 0, addressable
        for blk in pinned:
            self.free_block(blk)

    def abort_adoption(
        self, staged: list[tuple[int, int]], pinned: list[int]
    ) -> None:
        for _, blk in staged:
            self.free_block(blk)
        for blk in pinned:
            self.free_block(blk)

    def _reload_from_host(self, h: int) -> tuple[int | None, str]:
        """Host-tier continuation of a prefix match: allocate an HBM block
        and upload hash h's offloaded pages into it. Returns (block, rung)
        where rung is "host" (ring hit) or "disk" (promoted off the disk
        tier) — the hydration-attribution distinction."""
        if self.host_tier is None or h not in self.host_tier:
            return None, ""
        blk = self.allocate()  # may itself evict (and offload) another block
        if blk is None:
            return None, ""
        source = self.host_tier.reload_into(h, blk)
        if not source:  # raced an eviction
            self.free_block(blk)
            return None, ""
        self._hash_to_block[h] = blk
        self._block_to_hash[blk] = h
        return blk, source

    def match_length(
        self, token_ids: list[int], parent: int | None = None
    ) -> int:
        """Matched-prefix length in TOKENS across both tiers, without taking
        references or moving any data — the /kv/lookup probe the KV-aware
        router depends on (reference: LMCache LookupMsg, routing_logic.py:
        222-344; gateway kv_aware_picker.go:90-133)."""
        if not self.enable_prefix_caching:
            return 0
        n = 0
        hashes = list(
            self._chain(token_ids, _ROOT_HASH if parent is None else parent)
        )
        for idx, h in enumerate(hashes):
            if h not in self._hash_to_block and (
                self.host_tier is None or h not in self.host_tier
            ):
                remote = getattr(self.host_tier, "remote", None)
                if remote is not None:
                    # continue the probe into the remote store: one batched
                    # contains round trip, no data movement
                    n += self.block_size * remote.contains_run(hashes[idx:])
                break
            n += self.block_size
        return n

    def _acquire(self, blk: int) -> None:
        if blk in self._ref:
            self._ref[blk] += 1
        else:
            self._ref[blk] = 1
            self._evictable.pop(blk, None)

    def register_full_block(
        self, blk: int, parent_hash: int, tokens: tuple[int, ...]
    ) -> int:
        """Make a freshly computed full block content-addressable. Returns the
        chain hash to use as the next block's parent."""
        h = chain_hash(parent_hash, tokens)
        if not self.enable_prefix_caching:
            return h
        if h not in self._hash_to_block:
            self._hash_to_block[h] = blk
            self._block_to_hash[blk] = h
            if self.events is not None and not (
                self.host_tier is not None and h in self.host_tier
            ):
                # a host-tier-resident hash is already published; re-entering
                # HBM changes nothing about cluster-level matchability
                self.events.emit_admit(h, parent_hash)
        return h

    @staticmethod
    def root_hash() -> int:
        return _ROOT_HASH

    def reset_stats(self) -> None:
        self.stats = CacheStats()

    def clear_prefix_cache(self) -> None:
        """Drop all content-addressing state. MUST be called whenever the
        device-side pool is reinitialized (sleep/wake, weight reload): the
        hashes describe KV bytes that no longer exist, and serving a match
        against a zeroed page would silently corrupt attention."""
        if self._ref:
            raise RuntimeError(
                "cannot clear prefix cache while blocks are referenced"
            )
        self._hash_to_block.clear()
        self._block_to_hash.clear()
        for blk in self._evictable:
            self._free.append(blk)
        self._evictable.clear()
        if self.events is not None:
            self.events.emit_clear()
            if self.host_tier is not None:
                # ring/disk entries survive the device-pool rebuild (bytes
                # are bytes, keyed by content hash) and stay matchable —
                # re-admit them so the cluster index's clear doesn't
                # under-report
                for h in self.host_tier.resident_hashes():
                    self.events.emit_admit(h, 0)

    # -- cluster KV index events (engine/kv_events.py) ---------------------

    def _on_host_drop(self, h: int) -> None:
        """The host ring or the disk tier dropped hash h: emit a cluster
        evict only if no local tier (HBM / ring / disk) still holds it —
        a ring→disk demotion or a drop of a copy keeps it matchable."""
        if (
            self.events is not None
            and h not in self._hash_to_block
            and (self.host_tier is None or h not in self.host_tier)
        ):
            self.events.emit_evict(h)

    def published_hashes(self) -> list[int]:
        """Every hash `match_length` would currently count from local tiers
        (HBM + host ring + disk) — the full-resync snapshot for the cluster
        KV index. Call with the pool quiesced (engine lock held)."""
        hashes = set(self._hash_to_block)
        if self.host_tier is not None:
            hashes.update(self.host_tier.resident_hashes())
        return list(hashes)

    def snapshot_events(self) -> tuple[str, int, list[int]]:
        """(epoch, seq, hashes) for a consistent index resync. The event
        buffer is NOT cleared — with publisher fan-out other subscribers
        may still need the buffered events; the publisher's per-subscriber
        cursors skip anything at or below `seq` for the subscriber this
        snapshot heals, so nothing double-applies. Call with the pool
        quiesced (engine lock held)."""
        if self.events is None:
            raise RuntimeError("prefix caching (and its event log) disabled")
        seq = self.events.snapshot_mark()
        return self.events.epoch, seq, self.published_hashes()
