"""The program's dense GQA decoder with a MemCom compressor, built from a
configuration file (Hugging Face key names) and filled with the seeded
weights of :mod:`chipbench.weights`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights as W


def program_config(c: dict):
    from repro.config import (LayerDesc, LayerLayout, MemComConfig,
                              ModelConfig)

    return ModelConfig(
        name=c["name"], family="dense",
        layout=LayerLayout.uniform(LayerDesc("attn", "dense"),
                                   c["num_hidden_layers"]),
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c.get("head_dim", 0),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        max_seq=c["max_position_embeddings"], dtype=c["torch_dtype"],
        memcom=MemComConfig(num_memory_tokens=c["num_memory_tokens"]))


def _name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def program_weights(cfg, seed: int):
    """``(target, compressor)`` in the program's layout and served dtype,
    made on the device in one jitted call.  Source-LLM, Memory-LLM and
    target get weights of their own (the program's own init copies the
    target into both)."""
    from repro.core import memcom

    shapes = jax.eval_shape(lambda: memcom.init_models(cfg, seed=0))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        {"target": shapes[0], **shapes[1]})

    def build(key):
        out = []
        for path, s in flat:
            name = _name(path)
            if "/period/" in name:
                out.append(W.stacked(key, name, s.shape[0], s.shape[1:],
                                     s.dtype))
            else:
                out.append(W.leaf(key, name, None, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    made = jax.jit(build)(W.root_key(seed))
    target = made.pop("target")
    return target, made
