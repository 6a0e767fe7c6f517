"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
chip that is described rather than attached: these tests catch what
interpret mode cannot (block shapes off the (8, 128) tiling, kernels
that overrun VMEM) without a chip.  Shapes are the real ones: the
smollm-360m heads (15 query / 5 KV heads of 64) on a 3,072-token shot
set, 8-slot decode (flash, paged pool and dense stripes), and the MemCom cross-attention at d_model 960
(smollm-360m) and 2304 (gemma2-2b) over m = 512 memory slots.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import memcom_xattn as mx
from repro.kernels import paged_attention as pa

HQ, HKV, HD = 15, 5, 64  # smollm-360m attention heads
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # loading the TPU library below would otherwise log under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B,Sq,Skv", [
    (1, 3072, 3072),  # Source-LLM prefill over a 3k-token shot set
    (8, 1, 576),      # dense-layout decode, 8 slots, S = 1 padded to 8
])
def test_flash_attention_compiles(one_chip, B, Sq, Skv):
    def fn(q, k, v, q_pos, kv_pos):
        return fa.flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                  return_lse=True)

    text = _compile(fn, one_chip, ((B, Sq, HQ, HD), BF16),
                    ((B, Skv, HKV, HD), BF16), ((B, Skv, HKV, HD), BF16),
                    ((B, Sq), I32), ((B, Skv), I32))
    assert "tpu_custom_call" in text


def test_paged_decode_compiles(one_chip):
    B, bs, nb, N = 8, 16, 36, 300

    def fn(q, k_pool, v_pool, tables, lengths):
        return pa.paged_flash_decode(q, k_pool, v_pool, block_tables=tables,
                                     lengths=lengths)

    text = _compile(fn, one_chip, ((B, 1, HQ, HD), BF16),
                    ((N, bs, HKV, HD), BF16), ((N, bs, HKV, HD), BF16),
                    ((B, nb), I32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_dense_decode_compiles(one_chip):
    """Dense-layout decode: 8 slots' 576-row stripes read as paged blocks."""
    B, L = 8, 576

    def fn(q, k, v, lengths):
        return pa.dense_flash_decode(q, k, v, lengths=lengths)

    text = _compile(fn, one_chip, ((B, 1, HQ, HD), BF16),
                    ((B, L, HKV, HD), BF16), ((B, L, HKV, HD), BF16),
                    ((B,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("D", [960, 2304])
def test_memcom_xattn_compiles(one_chip, D):
    m, t = 512, 3072
    text = _compile(lambda q, k, v: mx.memcom_xattn(q, k, v), one_chip,
                    ((1, m, D), BF16), ((1, t, D), BF16), ((1, t, D), BF16))
    assert "tpu_custom_call" in text
