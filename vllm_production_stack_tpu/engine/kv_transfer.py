"""KV page transfer between engines (disaggregated prefill fabric).

The reference ships KV pages prefill→decode with NIXL over UCX
(GPU-direct/RDMA when available, TCP otherwise — deployment-vllm-multi.yaml:
267-305, values-16-disagg-prefill.yaml). The TPU stack's transfer is
content-addressed: a prompt's full blocks are identified by the same chain
hashes the prefix cache uses, so "shipping KV" is exporting (hash, pages)
pairs from the prefill engine's pool and adopting them into the decode
engine's pool — after which the decode request is an ordinary 100% prefix
hit.

Transports: this module defines the wire format (self-delimiting
dtype-tagged frames — the same framing the kvstore and peer paths use)
served over the engines' HTTP surface (/kv/export, /kv/import, /kv/pull).
On multi-slice TPU deployments
the same export/adopt protocol can ride jax device-to-device transfers over
ICI instead of host-staged HTTP — the pool-side bookkeeping (this module)
is transport-agnostic, exactly like the reference's NIXL sender/receiver
split from LMCache's bookkeeping.
"""

from __future__ import annotations

import io
import json
import struct

import numpy as np

from .kv_codec import (  # noqa: F401  (np_dtype_from_name re-exported)
    EncodedKVBlock,
    KVDtypeError,
    decode_payload,
    np_dtype_from_name,
)
from ..utils.logging import init_logger

logger = init_logger(__name__)


# -- streaming wire format ---------------------------------------------------
#
# The fast PD path: instead of staging the whole prompt's KV as one npz
# (hundreds of MB at 32k context — VERDICT r2 weak #3), blocks travel as
# self-delimiting frames over a chunked HTTP response:
#     4-byte LE header length | JSON header | raw page bytes
# so the receiver can adopt block i while block i+1 is still in flight, and
# the sender can stream device→host copies straight onto the socket without
# ever materializing the full tensor.


def raw_frame(
    h, raw: bytes, dtype_name: str, shape: list[int],
    codec: str = "", group: int = 0, scale_nbytes: int = 0,
) -> bytes:
    """Frame pre-serialized block bytes (the kvstore server streams stored
    payloads without reconstructing arrays). `dtype`/`shape` are always the
    LOGICAL geometry; when `codec` is set the payload is at-rest encoded
    (int4 scales||codes or fp8 bytes) and the extra header fields carry
    what FrameParser needs to dequantize it."""
    head = {
        "hash": str(h),
        "dtype": dtype_name,
        "shape": list(shape),
        "nbytes": len(raw),
    }
    if codec:
        head["codec"] = codec
        head["group"] = int(group)
        head["scale_nbytes"] = int(scale_nbytes)
    head_b = json.dumps(head).encode()
    return struct.pack("<I", len(head_b)) + head_b + raw


def block_frame(h: int, arr: np.ndarray) -> bytes:
    """One streamed KV block. The raw bytes are the array's own buffer (one
    tobytes copy — no npz container, no re-stacking)."""
    view = np.ascontiguousarray(arr)
    return raw_frame(h, view.tobytes(), arr.dtype.name, list(arr.shape))


def encoded_frame(h: int, obj) -> bytes:
    """One streamed at-rest block: EncodedKVBlock frames carry their codec
    metadata, plain ndarrays degrade to block_frame — tier writers call
    this with whatever form the block is in (a ring-encoded block flows to
    disk/remote WITHOUT a decode+re-encode round trip)."""
    if isinstance(obj, EncodedKVBlock):
        return raw_frame(
            h, obj.payload, obj.dtype, list(obj.shape),
            codec=obj.codec, group=obj.group, scale_nbytes=obj.scale_nbytes,
        )
    return block_frame(h, obj)


class FrameParser:
    """Incremental parser for the streamed format: feed() network chunks in,
    get complete (hash, array) blocks out.

    `max_frame_bytes` bounds both the JSON header and the payload a single
    frame may claim (default 256 MiB — far above any real KV block, far below
    anything that could exhaust RAM). A corrupted or hostile stream claiming
    a huge frame fails fast with ValueError instead of making the receiver
    buffer the entire remaining response as residual bytes."""

    def __init__(
        self, max_frame_bytes: int = 256 << 20, decode_codec: bool = True
    ):
        self._buf = bytearray()
        self.max_frame_bytes = max_frame_bytes
        # first parse failure in partial mode (feed_partial); once set,
        # the parser is dead — further feeds return nothing
        self.error: Exception | None = None
        # decode_codec=True (default): codec-tagged frames dequantize to
        # logical arrays right here, so every legacy consumer keeps
        # seeing ndarrays. False: they come back as EncodedKVBlock and
        # the dequant is deferred to the pool's adopt boundary (the
        # fetch paths use this — landed chunks hold WIRE bytes in RAM).
        self.decode_codec = decode_codec
        # (wire payload bytes, logical bytes) per yielded frame, in
        # yield order — the flow meter's wire-vs-logical accounting
        self.frame_meta: list[tuple[int, int]] = []

    def _next_frame(self) -> tuple[int, np.ndarray] | None:
        """Parse ONE complete frame off the buffer, None if the buffered
        bytes don't yet hold a whole frame. Raises on a corrupt frame."""
        if len(self._buf) < 4:
            return None
        head_len = struct.unpack_from("<I", self._buf)[0]
        if head_len > self.max_frame_bytes:
            raise ValueError(
                f"frame header claims {head_len} bytes "
                f"(max {self.max_frame_bytes}) — corrupt stream"
            )
        if len(self._buf) < 4 + head_len:
            return None
        head = json.loads(bytes(self._buf[4 : 4 + head_len]))
        nbytes = int(head["nbytes"])
        if nbytes < 0 or nbytes > self.max_frame_bytes:
            raise ValueError(
                f"frame payload claims {nbytes} bytes "
                f"(max {self.max_frame_bytes}) — corrupt stream"
            )
        total = 4 + head_len + nbytes
        if len(self._buf) < total:
            return None
        raw = bytes(self._buf[4 + head_len : total])
        del self._buf[:total]
        codec = head.get("codec", "")
        if codec:
            enc = EncodedKVBlock(
                codec, int(head.get("group", 0)), head["dtype"],
                tuple(int(d) for d in head["shape"]), raw,
                int(head.get("scale_nbytes", 0)),
            )
            # resolve the logical dtype NOW even on the deferred path: a
            # frame this host can't decode must die in the parser (clean
            # degraded miss) rather than at adopt time on the step thread
            self.frame_meta.append((len(raw), enc.logical_nbytes))
            if self.decode_codec:
                return (int(head["hash"]), decode_payload(
                    codec, enc.group, enc.dtype, enc.shape, raw,
                    enc.scale_nbytes,
                ))
            return (int(head["hash"]), enc)
        arr = np.frombuffer(
            raw, dtype=np_dtype_from_name(head["dtype"])
        ).reshape(head["shape"])
        self.frame_meta.append((len(raw), arr.nbytes))
        return (int(head["hash"]), arr)

    def feed(self, data: bytes) -> list[tuple[int, np.ndarray]]:
        self._buf.extend(data)
        out: list[tuple[int, np.ndarray]] = []
        while (frame := self._next_frame()) is not None:
            out.append(frame)
        return out

    def feed_partial(self, data: bytes) -> list[tuple[int, np.ndarray]]:
        """Like feed(), but a corrupt frame KEEPS the frames completed
        before it instead of discarding the whole batch: the valid prefix
        is real data (the remote fetch path promotes it — losing it would
        turn a one-frame corruption into a full-run cache miss, and lose
        the timing of blocks that actually moved). `self.error` carries
        the failure; the parser is dead afterwards."""
        if self.error is not None:
            return []
        self._buf.extend(data)
        out: list[tuple[int, np.ndarray]] = []
        while True:
            try:
                frame = self._next_frame()
            except Exception as e:  # corrupt header/payload claim/dtype
                self.error = e
                break
            if frame is None:
                break
            out.append(frame)
        return out

    @property
    def residual(self) -> int:
        return len(self._buf)


def serialize_blocks(
    hashes: list[int], blocks: np.ndarray, fingerprint: str = ""
) -> bytes:
    """One-shot export payload (/kv/export → /kv/import): a JSON manifest
    frame {fingerprint, count} followed by one dtype-tagged block frame
    per hash — the SAME framing the kvstore/mget/peer paths speak, so
    ml_dtypes pools (bf16, fp8) ship natively instead of through the old
    npz detour's uint bit-pattern views (npz can't carry ml_dtypes)."""
    manifest = json.dumps({
        "fingerprint": fingerprint, "count": len(hashes),
    }).encode()
    frames = [struct.pack("<I", len(manifest)) + manifest]
    frames.extend(block_frame(h, arr) for h, arr in zip(hashes, blocks))
    return b"".join(frames)


def deserialize_blocks(payload: bytes) -> tuple[list[int], np.ndarray, str]:
    if payload[:2] == b"PK":  # legacy npz export from a pre-frame sender
        return _deserialize_blocks_npz(payload)
    head_len = struct.unpack_from("<I", payload)[0]
    manifest = json.loads(payload[4 : 4 + head_len])
    frames = FrameParser().feed(payload[4 + head_len:])
    if len(frames) != int(manifest.get("count", len(frames))):
        raise ValueError(
            f"KV export payload truncated: manifest promises "
            f"{manifest.get('count')} blocks, parsed {len(frames)}"
        )
    hashes = [h for h, _ in frames]
    if not frames:
        return [], np.empty((0,)), str(manifest.get("fingerprint", ""))
    blocks = np.stack([arr for _, arr in frames])
    return hashes, blocks, str(manifest.get("fingerprint", ""))


def _deserialize_blocks_npz(payload: bytes):
    """Read the pre-frame npz export format (rolling-upgrade peers)."""
    with np.load(io.BytesIO(payload)) as z:
        hi_lo = z["hashes"]
        blocks = z["blocks"]
        dtype = str(z["dtype"])
        fingerprint = str(z["fingerprint"]) if "fingerprint" in z else ""
    if dtype in ("bfloat16", "float8_e4m3fn"):
        blocks = blocks.view(np_dtype_from_name(dtype))
    hashes = [int(hi) << 64 | int(lo) for hi, lo in hi_lo]
    return hashes, blocks, fingerprint


def _page_leaf(runner):
    """The first layer's pool that holds pages (a layer that holds state has
    a dict of state pools — with its page array under "kv" where it holds
    pages too — and every KV tier is refused beside those)."""
    return next(kv["kv"] if isinstance(kv, dict) else kv
                for kv in runner.kv_caches
                if not isinstance(kv, dict) or "kv" in kv)


def engine_block_shape(runner) -> tuple[int, ...]:
    """(L, 2, block_size, kvH, D) — the page geometry of one engine's pool.
    The ONE definition shared by import validation (KVTransfer), remote-match
    validation (KVBlockPool.expected_block_shape) and the stream receiver's
    frame-size bound: a layout change lands everywhere or nowhere."""
    leaf = _page_leaf(runner)
    return (len(runner.kv_caches), 2, leaf.shape[2], *leaf.shape[3:])


def engine_block_nbytes(runner) -> int:
    """Bytes of one KV block as stored in this engine's pool."""
    shape = engine_block_shape(runner)
    n = 1
    for d in shape:
        n *= int(d)
    return n * _page_leaf(runner).dtype.itemsize


class KVTransfer:
    """Pool-side export/adopt bookkeeping, bound to one engine's scheduler
    pool + runner. All methods assume the caller holds the engine lock."""

    def __init__(self, pool, runner):
        self.pool = pool
        self.runner = runner

    def block_shape(self) -> tuple[int, ...]:
        """(L, 2, block_size, kvH, D) — the only page geometry this engine
        can adopt."""
        return engine_block_shape(self.runner)

    def export_prompt(
        self, token_ids: list[int], parent: int | None = None
    ) -> tuple[list[int], np.ndarray]:
        """(hashes, pages) for the prompt's HBM-resident full blocks —
        called on the prefill engine right after its max_tokens=1 pass.
        All fetches dispatch before any resolves, so the device→host copies
        pipeline instead of serializing under the engine lock."""
        root = self.pool.root_hash() if parent is None else parent
        pending: list[tuple[int, list]] = []
        for h in self.pool._chain(list(token_ids), root):
            blk = self.pool._hash_to_block.get(h)
            if blk is None:
                break
            pending.append((h, self.runner.fetch_block(blk)))
        if not pending:
            return [], np.empty((0,))
        hashes = [h for h, _ in pending]
        data = [
            np.stack([np.asarray(p) for p in parts]) for _, parts in pending
        ]
        return hashes, np.stack(data)

    def export_prompt_lazy(
        self, token_ids: list[int], parent: int | None = None
    ) -> tuple[list[int], list[list]]:
        """(hashes, per-block device slices) for the prompt's resident full
        blocks — the STREAMING sender path. Only dispatches the device→host
        copies (fast, under the engine lock); the caller resolves each
        block's numpy OFF the lock while writing earlier blocks to the
        socket, so transfer pipelines with both the copies and decode."""
        root = self.pool.root_hash() if parent is None else parent
        hashes: list[int] = []
        parts: list[list] = []
        for h in self.pool._chain(list(token_ids), root):
            blk = self.pool._hash_to_block.get(h)
            if blk is None:
                break
            hashes.append(h)
            parts.append(self.runner.fetch_block(blk))
        return hashes, parts

    def contains_hashes(self, hashes: list[int]) -> int:
        """How many of `hashes` (in order, consecutively) this engine can
        serve from its local tiers (HBM + host ring + disk) — the
        /kv/peer_contains probe (docs/35-peer-kv-reuse.md). No data
        moves; pure GIL-atomic dict/containment walks, callable with OR
        without the engine lock (the probe is staleness-tolerant — the
        fetch/adoption path re-validates everything)."""
        host = self.pool.host_tier
        n = 0
        for h in hashes:
            if h in self.pool._hash_to_block or (
                host is not None and h in host
            ):
                n += 1
            else:
                break
        return n

    def export_hashes(self, hashes: list[int]):
        """(served, entries) for the consecutive locally-resident prefix of
        an ARBITRARY hash run — the /kv/peer_fetch sender half. Under the
        engine lock this only dispatches device→host copies (HBM blocks)
        and grabs ring references; entries resolve to numpy OFF the lock:

        - ("dev", parts)  — HBM block, per-layer device slices in flight
        - ("np", array)   — host-ring bytes, already resolved
        - ("disk", hash)  — disk-resident; the caller loads the file off
          the lock (DiskKVTier is fetch-thread-safe) so a multi-MB read
          never stalls the step thread's admissions
        """
        host = self.pool.host_tier
        served: list[int] = []
        entries: list[tuple[str, object]] = []
        for h in hashes:
            blk = self.pool._hash_to_block.get(h)
            if blk is not None:
                entries.append(("dev", self.runner.fetch_block(blk)))
            elif host is not None and len(host) and h in host._data:
                arr = host.peek_bytes(h)
                if arr is None:
                    break
                entries.append(("np", arr))
            elif (
                host is not None
                and host.disk is not None
                and h in host.disk
            ):
                entries.append(("disk", h))
            else:
                break
            served.append(h)
        return served, entries

    def import_blocks(self, hashes: list[int], blocks: np.ndarray) -> int:
        """Adopt shipped pages into this engine's pool as evictable cached
        blocks. Returns blocks actually adopted (already-resident and
        pool-full blocks are skipped; a partial import still shortens the
        decode engine's recompute)."""
        want = self.block_shape()
        if len(hashes) and tuple(blocks.shape[1:]) != want:
            raise ValueError(
                f"KV page geometry mismatch: got {tuple(blocks.shape[1:])}, "
                f"this engine needs {want}"
            )
        # allocate + upload the whole group in ONE device dispatch
        # (upload_blocks): per-block uploads cost a dispatch round trip
        # each, which dominates PD transfer.
        # Staging/commit bookkeeping is the pool's shared definition
        # (kv_cache.stage_adoption — also used by the device path)
        by_hash = {h: d for h, d in zip(hashes, blocks)}
        staged, pinned = self.pool.stage_adoption(hashes)
        if not staged:
            self.pool.abort_adoption(staged, pinned)
            return 0
        try:
            upload_many = getattr(self.runner, "upload_blocks", None)
            if upload_many is not None:
                upload_many(
                    [blk for _, blk in staged],
                    np.stack([by_hash[h] for h, _ in staged]),
                )
            else:
                for h, blk in staged:
                    self.runner.upload_block(blk, by_hash[h])
        except Exception:
            self.pool.abort_adoption(staged, pinned)
            raise
        self.pool.commit_adoption(staged, pinned)
        return len(staged)
