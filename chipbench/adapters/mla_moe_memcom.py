"""The program's DeepSeek-V2 decoder (multi-head latent attention, a
leading dense block, then mixture-of-experts blocks holding one share of
the experts) with a MemCom compressor, built from a configuration file
(Hugging Face key names) and filled with the seeded weights of
:mod:`chipbench.weights`.

``n_routed_experts`` is the number of experts held here, starting at
``expert_offset``; the router keeps the published width
``published["n_routed_experts"]``.  Weights are made one leaf at a time,
so the largest transient is the float32 draw of one stacked leaf, not of
the whole model.
"""

from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import weights as W


def program_config(c: dict):
    from repro.config import (LayerDesc, LayerLayout, MemComConfig,
                              MLAConfig, ModelConfig, MoEConfig, YarnScaling)

    dense = c["first_k_dense_replace"]
    rs = c["rope_scaling"]
    assert rs["type"] == "yarn", rs
    # the program has no gate scale: DeepSeek-V2-Lite publishes 1
    assert c["routed_scaling_factor"] == 1, c["routed_scaling_factor"]
    return ModelConfig(
        name=c["name"], family="moe",
        layout=LayerLayout(prefix=(LayerDesc("mla", "dense"),) * dense,
                           period=(LayerDesc("mla", "moe"),),
                           repeats=c["num_hidden_layers"] - dense),
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                      q_lora_rank=c["q_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(num_experts=c["published"]["n_routed_experts"],
                      top_k=c["num_experts_per_tok"],
                      expert_d_ff=c["moe_intermediate_size"],
                      num_shared_experts=c["n_shared_experts"],
                      shared_d_ff=c["moe_intermediate_size"],
                      norm_topk_prob=bool(c["norm_topk_prob"]),
                      held_experts=c["n_routed_experts"],
                      expert_offset=c["expert_offset"]),
        rope_theta=float(c["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        max_seq=c["max_position_embeddings"], dtype=c["torch_dtype"],
        memcom=MemComConfig(num_memory_tokens=c["num_memory_tokens"]))


def _name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def std(name: str, shape) -> float:
    """:func:`chipbench.weights.leaf`'s scale for a matrix or row table."""
    if name.endswith(W._ROWS):
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5


@partial(jax.jit, static_argnames=("shape", "dtype", "layers"))
def _draw(key, tag, scale, *, shape, dtype, layers):
    """:func:`chipbench.weights.leaf` (``layers`` None) or ``stacked``,
    with the name's hash and the scale traced, so that every leaf of one
    shape shares one compiled program."""
    k = jax.random.fold_in(key, tag)

    def one(layer):
        kk = k if layer is None else jax.random.fold_in(k, layer)
        return (jax.random.normal(kk, shape, jnp.float32) * scale
                ).astype(dtype)

    if layers is None:
        return one(None)
    return jax.vmap(one)(jnp.arange(layers, dtype=jnp.int32))


def leaf(key, name: str, shape, dtype, layers=None):
    """One seeded leaf: norm gains (every 1-D leaf) are ones, every other
    leaf is the value :func:`chipbench.weights.leaf` gives its name."""
    per_layer = tuple(shape[1:]) if layers is not None else tuple(shape)
    if len(per_layer) == 1:
        return jnp.ones(shape, dtype)
    tag = jnp.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _draw(key, tag, jnp.float32(std(name, per_layer)),
                 shape=per_layer, dtype=jnp.dtype(dtype), layers=layers)


def program_weights(cfg, seed: int):
    """``(target, compressor)`` in the program's layout and served dtype,
    made on the device leaf by leaf.  Source-LLM, Memory-LLM and target
    get weights of their own (the program's own init copies the target
    into both)."""
    from repro.core import memcom

    shapes = jax.eval_shape(lambda: memcom.init_models(cfg, seed=0))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        {"target": shapes[0], **shapes[1]})
    key = W.root_key(seed)
    out = []
    for path, s in flat:
        name = _name(path)
        layers = s.shape[0] if "/period/" in name else None
        out.append(jax.block_until_ready(
            leaf(key, name, s.shape, s.dtype, layers)))
    made = jax.tree_util.tree_unflatten(tree, out)
    target = made.pop("target")
    return target, made
