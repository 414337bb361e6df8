"""The window kernels (and the full kernels at Laguna-S-2.1's head counts)
compiled by the installed TPU compiler for a DESCRIBED v5e topology — no
chip attached, nothing runs (tests/test_chip_compile.py says why such a
guard exists): the cell's shapes, 16 rows x a 1,024-page table, a
2,048-token chunk, 48 and 72 query heads on 8 kv heads x 128, bf16 and fp8
pools (the reference check's fp8 arm)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_production_stack_tpu.ops import paged_attention_pallas as pk

from test_chip_compile import _assert_kernel, topo  # noqa: F401

KVH, D, BLOCK, NB, WINDOW = 8, 128, 32, 1024, 512


def _sds(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("nh,window", [(72, WINDOW), (48, 0)])
def test_decode_kernels_compile_at_the_cells_shapes(
        topo, nh, window, kv_dtype):  # noqa: F811
    s, rows, w = _sds(topo), 16, 8
    args = (
        s((rows, nh, D), jnp.bfloat16),
        s((2, 1329, BLOCK, KVH, D), jnp.dtype(kv_dtype)),
        s((rows, NB), jnp.int32), s((rows,), jnp.int32),
        s((w, rows, KVH, D), jnp.bfloat16),
        s((w, rows, KVH, D), jnp.bfloat16), s((), jnp.int32),
    )
    fn = (
        (lambda *a: pk.paged_window_decode_attention(
            *a, scale=D ** -0.5, window=window)) if window
        else (lambda *a: pk.paged_decode_attention(*a, scale=D ** -0.5)))
    compiled = jax.jit(fn).lower(*args).compile()
    _assert_kernel(compiled)
    if window:
        assert "paged_window_decode_attention" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("nh,window", [(72, WINDOW), (48, 0)])
@pytest.mark.parametrize("rows", [1, 2])
def test_prefill_kernels_compile_at_the_cells_shapes(
        topo, nh, window, rows, kv_dtype):  # noqa: F811
    s, chunk = _sds(topo), 2048
    args = (
        s((rows, chunk, nh, D), jnp.bfloat16),
        s((2, 1329, BLOCK, KVH, D), jnp.dtype(kv_dtype)),
        s((rows, NB), jnp.int32), s((rows,), jnp.int32),
        s((rows,), jnp.int32),
    )
    fn = (
        (lambda *a: pk.paged_window_prefill_attention(
            *a, scale=D ** -0.5, window=window)) if window
        else (lambda *a: pk.paged_prefill_attention(*a, scale=D ** -0.5)))
    compiled = jax.jit(fn).lower(*args).compile()
    _assert_kernel(compiled)
    if window:
        assert "paged_window_prefill_attention" in compiled.as_text()
