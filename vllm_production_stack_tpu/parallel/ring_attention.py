"""Ring attention: sequence/context parallelism for long-context prefill.

The reference stack inherits long-context support from its engines (vLLM
context-parallel / chunked prefill); on TPU the idiomatic construction is a
ring over the "sp" mesh axis (SURVEY §2.4 parallelism map):

  - the sequence axis of Q, K and V is sharded over sp devices;
  - each device computes flash-style (online-softmax) attention of its LOCAL
    query shard against the K/V shard it currently holds, then rotates the
    K/V shard to its ring neighbour with `lax.ppermute`;
  - after sp-1 hops every query shard has seen every K/V shard, and the
    online softmax has combined the partials exactly as one softmax would.

Peak memory per device is O(T/sp) for K/V and one (Tq_local, Tkv_local)
score block — never the (T, T) score matrix — and the ppermute rides
nearest-neighbour ICI because sp is adjacent to tp in the mesh grid
(parallel/mesh.py). Composes with tp: heads shard over tp inside the same
shard_map, and the only collective over sp is the ppermute itself.

For CHUNKED prefill (continuing a partially-computed sequence) the ring also
seeds its online softmax with a pooled-history block: every query shard
attends the sequence's already-resident paged KV (positions < hist_len)
before the ring starts — so the engine's sp path supports the same
chunk-by-chunk prefill contract as the paged XLA path.

No counterpart file exists in the reference (it ships no model/engine code);
behaviourally this replaces the NCCL context-parallel path of its served
engines with XLA collectives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DP_AXIS, SP_AXIS, TP_AXIS

NEG_INF = -1e30


def _ring_attention_local(
    q: jax.Array,  # (B, Tq, nh_local, D) this device's query shard
    k: jax.Array,  # (B, Tkv, kvh_local, D) the K shard currently held
    v: jax.Array,  # (B, Tkv, kvh_local, D)
    q_pos: jax.Array,  # (B, Tq) int32 GLOBAL positions of local queries
    kv_pos: jax.Array,  # (B, Tkv) int32 global positions of held K/V
    kv_valid: jax.Array,  # (B, Tkv) bool: held K/V is a real token
    hist_k: jax.Array | None,  # (B, S, kvh_local, D) pooled history, or None
    hist_v: jax.Array | None,
    hist_len: jax.Array | None,  # (B,) pool positions < hist_len are history
    *,
    axis_name: str,
    scale: float,
) -> jax.Array:
    """Per-device body (runs under shard_map). Causality is evaluated on
    GLOBAL positions carried alongside the K/V shard, so any contiguous or
    striped sequence layout is correct — the ring never needs to know which
    shard "came first"."""
    axis_size = jax.lax.psum(1, axis_name)
    b, tq, nh, d = q.shape
    kvh = k.shape[2]
    qpk = nh // kvh
    qg = q.reshape(b, tq, kvh, qpk, d).astype(jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def attend_block(k, v, mask, m_prev, l_prev, acc):
        # (B, kvH, qpk, Tq, Tkv) one shard-pair score block
        scores = (
            jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32)) * scale
        )
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bskd->bkgtd", p, v.astype(jnp.float32)
        )
        return m_new, l_new, acc

    state = (
        jnp.full((b, kvh, qpk, tq), NEG_INF, jnp.float32),
        jnp.zeros((b, kvh, qpk, tq), jnp.float32),
        jnp.zeros((b, kvh, qpk, tq, d), jnp.float32),
    )
    if hist_k is not None:
        # chunked prefill: every query attends ALL already-resident history
        # (history position j < hist_len, no causal test needed — history is
        # strictly before this chunk)
        s = hist_k.shape[1]
        hist_mask = jnp.broadcast_to(
            (jnp.arange(s, dtype=jnp.int32)[None, :] < hist_len[:, None])[
                :, None, :
            ],
            (b, tq, s),
        )  # (B, Tq, S) — attend_block's mask contract is always rank 3
        state = attend_block(hist_k, hist_v, hist_mask, *state)

    def chunk_mask(kv_pos, kv_valid):
        return kv_valid[:, None, :] & (
            kv_pos[:, None, :] <= q_pos[:, :, None]
        )  # (B, Tq, Tkv)

    # local block first, then rotate-then-attend (axis_size - 1) times: the
    # ring does exactly axis_size - 1 ppermute hops — the last shard is not
    # rotated onward just to be dropped
    state = attend_block(k, v, chunk_mask(kv_pos, kv_valid), *state)

    def body(carry, _):
        k, v, kv_pos, kv_valid, m, l, acc = carry
        # rotate the K/V shard (and its position metadata) around the ring
        k, v, kv_pos, kv_valid = (
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k, v, kv_pos, kv_valid)
        )
        m, l, acc = attend_block(k, v, chunk_mask(kv_pos, kv_valid), m, l, acc)
        return (k, v, kv_pos, kv_valid, m, l, acc), None

    (_, _, _, _, _, l, acc), _ = jax.lax.scan(
        body, (k, v, kv_pos, kv_valid, *state), None, length=axis_size - 1
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, nh, d).astype(q.dtype)


def ring_attention(
    mesh: Mesh,
    q: jax.Array,  # (B, T, num_heads, D), T sharded over sp
    k: jax.Array,  # (B, T, kvH, D)
    v: jax.Array,  # (B, T, kvH, D)
    q_pos: jax.Array,  # (B, T) int32 global positions
    kv_valid: jax.Array,  # (B, T) bool real-token mask
    *,
    scale: float,
    hist_k: jax.Array | None = None,  # (B, S, kvH, D) pooled history
    hist_v: jax.Array | None = None,
    hist_len: jax.Array | None = None,  # (B,) history length per row
) -> jax.Array:
    """Causal GQA attention with the sequence axis sharded over the mesh's
    sp axis (batch over dp, heads over tp). With hist_* given, queries also
    attend an already-computed paged-history block (replicated over sp —
    every query shard needs all history; O(S/tp) per device like the paged
    path). Numerically equivalent to ops.attention.masked_attention over the
    concatenated context, up to float associativity. On an sp=1 mesh it
    degrades to one local flash block."""
    qspec = P(DP_AXIS, SP_AXIS, TP_AXIS, None)
    pspec = P(DP_AXIS, SP_AXIS)
    hspec = P(DP_AXIS, None, TP_AXIS, None)
    fn = functools.partial(
        _ring_attention_local, axis_name=SP_AXIS, scale=scale
    )
    if hist_k is None:
        in_specs = (qspec, qspec, qspec, pspec, pspec, pspec, None, None, None)
        args = (q, k, v, q_pos, q_pos, kv_valid, None, None, None)
    else:
        in_specs = (
            qspec, qspec, qspec, pspec, pspec, pspec,
            hspec, hspec, P(DP_AXIS),
        )
        args = (q, k, v, q_pos, q_pos, kv_valid, hist_k, hist_v, hist_len)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=qspec,
        check_vma=False,
    )(*args)
