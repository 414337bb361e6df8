"""A KDA layer's q, k, v in one Pallas pass (ops/kda_short_conv.py, in
interpret mode) against the XLA form it stands in for
(`models/llama.py:_Recurrence.qkv` over `_PackedRecurrence.conv`: the
convolution over each row's own tokens, SiLU, the q / k L2 norms) on a packed
axis: float32 in and out, so what is compared is what both compute BEFORE
the one cast to the model's dtype; the new tails bit for bit."""

import jax.numpy as jnp
import numpy as np
import pytest

from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.ops import kda_short_conv as ksc

TAPS = 4


def _chunk(n, lens, fresh, heads, d, dtype=jnp.float32, seed=0):
    """A packed chunk of `n` positions: x, the taps, a `conv` pool whose
    every slot holds garbage, and the recurrence's `st` (interpret unset)."""
    rng = np.random.RandomState(seed)
    b, c = len(lens), 3 * heads * d
    x = jnp.asarray(rng.randn(n, c), dtype)
    taps = jnp.asarray(rng.randn(TAPS, c) * 0.5, dtype)
    pool = jnp.asarray(rng.randn(2 * b + 2, TAPS - 1, c), dtype)
    lens = jnp.asarray(lens, jnp.int32)
    fresh = jnp.asarray(fresh)
    pack = llama.pack_index(lens, lens + jnp.where(fresh, 0, 7), n)
    st = {"read": jnp.arange(1, b + 1), "write": jnp.arange(b + 1, 2 * b + 1),
          "row_off": pack["row_off"], "tok_row": pack["tok_row"],
          "chunk_lens": lens, "fresh": fresh, "tap": None}
    return x, taps, pool, st


def _both(n, lens, fresh, heads, d, tile=None, **kw):
    """((q, k, v, pool) of the XLA form, the same of the pass)."""
    x, taps, pool, st = _chunk(n, lens, fresh, heads, d, **kw)
    out = []
    for interpret in (None, True):
        recur = llama._PackedRecurrence(
            {"conv": pool}, {**st, "interpret": interpret}, None)
        proj = tuple(jnp.split(x, 3, axis=-1))
        if tile is None or interpret is None:
            q, k, v = recur.qkv(proj, taps, heads, d)
        else:  # several token tiles on a short axis
            tail = recur._old_tail()
            corr, at, jj = recur._tail_terms(
                tail, taps.astype(jnp.float32), n)
            recur._keep_tail(lambda at: x[at], n, tail, jj)
            q, k, v = (z.reshape(n, heads, d) for z in ksc.kda_short_conv(
                *proj, taps, corr, at, st["row_off"], heads=heads,
                interpret=True, max_token_tile=tile))
        out.append((q, k, v, recur.pools["conv"]))
    return out


@pytest.mark.parametrize("n,lens,fresh,heads,d,tile", [
    pytest.param(128, (128,), (True,), 2, 64, None, id="one_row"),
    pytest.param(256, (100, 156), (False, True), 2, 128, None,
                 id="a_seam_inside_a_tile"),
    pytest.param(384, (128, 200), (True, False), 2, 64, 128,
                 id="a_seam_on_a_tiles_edge_rows_across_tiles"),
    pytest.param(192, (64, 100, 0), (False, True, False), 4, 32, None,
                 id="continued_fresh_padding"),
    pytest.param(128, (2, 1, 60, 0), (False, False, True, False), 2, 64,
                 None, id="rows_shorter_than_the_tail"),
    pytest.param(100, (40, 60), (False, True), 2, 64, None,
                 id="no_whole_number_of_sub_tiles"),
    pytest.param(64, (30, 34), (False, True), 64, 128, None,
                 id="solars_heads"),
    pytest.param(64, (64, 0), (False, False), 32, 128, None,
                 id="lings_heads"),
])
def test_the_pass_against_the_xla_form(n, lens, fresh, heads, d, tile):
    want, got = _both(n, lens, fresh, heads, d, tile)
    for name, w, g in zip("qkv", want, got):
        assert g.shape == w.shape == (n, heads, d) and g.dtype == w.dtype
        # (every position: the padding tokens' too, though none is read)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-6, atol=2e-6, err_msg=name)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


def test_the_model_dtypes_outputs_are_the_xla_forms():
    """bfloat16 in and out, as the cells run it: the pass's one cast rounds
    the same float32 sums (a rare last-place difference of the logistic
    aside: under one bfloat16 step, and under 0.1% of the values)."""
    want, got = _both(256, (90, 166), (False, True), 4, 128,
                      dtype=jnp.bfloat16)
    for w, g in zip(want[:3], got[:3]):
        assert g.dtype == jnp.bfloat16
        w, g = (np.asarray(z.astype(jnp.float32)) for z in (w, g))
        np.testing.assert_allclose(g, w, rtol=2 ** -7, atol=1e-6)
        assert np.mean(g != w) < 1e-3
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


@pytest.mark.parametrize("n,most,tile", [
    (2048, 2048, 2048), (4096, 2048, 2048), (192, 2048, 192),
    (384, 128, 128), (64, 2048, 64)])
def test_the_token_tile_divides_the_axis(n, most, tile):
    assert ksc.token_tile(n, most) == tile


@pytest.mark.parametrize("heads,d,per_tile", [
    (64, 128, 1), (32, 128, 1), (4, 64, 2), (4, 32, 4), (2, 16, 2)])
def test_a_head_tile_fills_whole_lanes(heads, d, per_tile):
    assert ksc.heads_per_tile(heads, d) == per_tile
