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
import re

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
                    ((N, bs, HKV * HD), BF16), ((N, bs, HKV * HD), BF16),
                    ((B, nb), I32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_paged_decode_stacked_compiles(one_chip):
    """The kernel reads layer ``layer`` of a stacked pool in place: the
    layer index is a scalar-prefetch operand, no slice of the stack is
    materialized for the call."""
    B, bs, nb, N, R = 8, 16, 36, 300, 4

    def fn(q, k_pool, v_pool, tables, lengths, layer):
        return pa.paged_flash_decode(q, k_pool, v_pool, block_tables=tables,
                                     lengths=lengths, layer=layer)

    text = _compile(fn, one_chip, ((B, 1, HQ, HD), BF16),
                    ((R, N, bs, HKV * HD), BF16), ((R, N, bs, HKV * HD), BF16),
                    ((B, nb), I32), ((B,), I32), ((), I32))
    assert "tpu_custom_call" in text
    assert not re.search(rf"= \S*\[{N},{bs},\S* (copy|dynamic-slice)\(", text)


def _dense_cfg(name, layers, d, hq, hkv, hd, d_ff, vocab, theta, tied):
    from repro.config import LayerDesc, LayerLayout, ModelConfig

    return ModelConfig(
        name=name, family="dense",
        layout=LayerLayout.uniform(LayerDesc("attn", "dense"), layers),
        d_model=d, num_heads=hq, num_kv_heads=hkv, head_dim=hd, d_ff=d_ff,
        vocab_size=vocab, rope_theta=theta, tie_embeddings=tied,
        max_seq=8192, dtype="bfloat16")


def _dsv2lite_2l():
    """DeepSeek-V2-Lite's widths, cut to its dense layer and one MoE layer
    holding 8 of the 64 experts, as one chip of the benchmark holds them."""
    import dataclasses

    from repro.config import LayerDesc, LayerLayout
    from repro.configs import get_config

    cfg = get_config("deepseek-v2-lite")
    return cfg.replace(
        layout=LayerLayout(prefix=(LayerDesc("mla", "dense"),),
                           period=(LayerDesc("mla", "moe"),), repeats=1),
        moe=dataclasses.replace(cfg.moe, held_experts=8))


# (config, pool blocks, block size, slots, table columns, prefill base)
POOL_CASES = {
    # smollm-360m widths: 15/5 heads of 64, 384-lane rows
    "smollm360m": (lambda: _dense_cfg("smollm-360m-2l", 2, 960, HQ, HKV, HD,
                                      2560, 49152, 1e5, True),
                   2241, 16, 32, 37, 512),
    # mistral-7b widths: 32/8 heads of 128, 1,024-lane rows
    "mistral7b": (lambda: _dense_cfg("mistral-7b-2l", 2, 4096, 32, 8, 128,
                                     14336, 32768, 1e6, False),
                  961, 16, 32, 53, 768),
    # deepseek-v2-lite widths: one 640-lane latent row [ckv|k_rope|0]
    "dsv2lite": (_dsv2lite_2l, 2305, 16, 256, 53, 768),
}


@pytest.mark.parametrize("case", list(POOL_CASES))
def test_paged_step_programs_update_the_pool_in_place(one_chip, monkeypatch,
                                                      case):
    """The serving engine's paged decode and prefill step programs (2
    layers at each configuration's widths, its pool, slots and table)
    update the KV pool in place: the cache is donated (its buffers alias
    the outputs), nothing copies or concatenates a pool, and no slice
    produces one layer's pool — only the kernel's block reads and the row
    writes touch it.  With every layer in the scanned period (the dense
    cases) no scatter yields one layer's pool either: the row writes go
    into the carried (layers, ...) stack; DeepSeek's leading dense layer
    has a pool of its own, which its row scatter updates in place.
    Arguments and results take the chip's default layouts, as the
    engine's arrays do."""
    from repro.kernels import ops
    from repro.models import transformer as tfm
    from repro.serving import ServingEngine
    from repro.serving import engine as engine_mod

    # build the programs as for the chip: the Pallas kernel lowered for
    # it (not the CPU interpreter) and the cache donated
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(engine_mod, "_donates_cache", lambda *_: True)
    make, N, bs, slots, cols, base = POOL_CASES[case]
    cfg = make()
    eng = ServingEngine(cfg, tfm.abstract_params(cfg), slots=slots,
                        max_len=cols * bs, kv_layout="paged", block_size=bs,
                        num_blocks=N, impl="pallas")

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    params, cache = shapes(eng.params), shapes(eng.cache)
    # a copy or concatenation of any pool or stack, or a slice (or, all
    # layers stacked, a scatter) that yields one layer's pool; the row
    # scatter into the carried (layers, ...) stack is in place (copy
    # insertion would put a copy before it otherwise)
    one_layer = "scatter|dynamic-slice" if not cfg.layout.prefix \
        else "dynamic-slice"
    pool = re.compile(
        rf"= \S*\[\S*{N},{bs},\S* (copy|copy-start|concatenate)\("
        rf"|= \S*\[(1,)?{N},{bs},\S* ({one_layer})\(")
    programs = {  # jitted step and its arguments (the prefill's base last)
        "decode": (eng._decode_greedy,
                   (params, cache, i32(slots, 1), i32(slots),
                    i32(slots, cols))),
        "prefill": (eng._prefill,
                    (params, cache, i32(1, 64), i32(), i32(cols), base)),
    }
    for name, (step, args) in programs.items():
        text = step.lower(*args).compile().as_text()
        header = text.split("\n", 1)[0]
        aliases = header.split("entry_computation_layout", 1)[0]
        assert aliases.count("may-alias") == len(jax.tree.leaves(cache)), (
            name, aliases)
        assert not pool.search(text), (name, pool.search(text).group(0))
        assert "tpu_custom_call" in text, name


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
