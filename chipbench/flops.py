"""Operations and bytes of the work the benchmark times, from shapes.

The per-token arithmetic is copied from ``repro/launch/costs.py``
(``_attn_flops``, ``_mlp_flops``, ``_logits_flops``) for the dense GQA
decoder, so that no change to
the program can move the yardstick; ``chipbench/tests`` checks the copies
against the originals.  A matmul (m x k)(k x n) costs 2mkn operations;
bytes are what a kernel must read and write at least, in bfloat16.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

BF16 = 2


@dataclass(frozen=True)
class Shape:
    """The sizes the arithmetic needs, named as in ``ModelConfig``."""

    d_model: int
    num_heads: int
    num_kv_heads: int
    hd: int
    d_ff: int
    vocab_size: int
    num_layers: int
    m: int

    @staticmethod
    def of(c: dict) -> "Shape":
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return Shape(c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], hd, c["intermediate_size"],
                     c["vocab_size"], c["num_hidden_layers"],
                     c["num_memory_tokens"])


# ---- copied from repro/launch/costs.py (dense attention layers) ----------


def attn_flops(cfg: Shape, n_q: float, ctx: float) -> float:
    d, nh, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    proj = 2 * d * nh * hd + 2 * 2 * d * nkv * hd + 2 * nh * hd * d
    attn = 4 * ctx * nh * hd  # scores + AV
    return n_q * (proj + attn)


def mlp_flops(cfg: Shape, n_q: float) -> float:
    return n_q * 6 * cfg.d_model * cfg.d_ff  # swiglu


def logits_flops(cfg: Shape, n_q: float) -> float:
    return 2 * n_q * cfg.d_model * cfg.vocab_size


# ---- the benchmark's own sums over recorded calls -----------------------


def attn_core_flops(cfg: Shape, pairs: float) -> float:
    """Scores and weighted sum for ``pairs`` (query, key) pairs, one
    layer: the part of ``attn_flops`` a flash or decode kernel does."""
    return 4 * pairs * cfg.num_heads * cfg.hd


def decode_step(cfg: Shape, ctx: np.ndarray) -> dict:
    """One batched decode step, each slot reading ``ctx`` cache rows
    (its prefix and everything after, the new token included)."""
    ctx = np.asarray(ctx, np.float64)
    B, L = len(ctx), cfg.num_layers
    kernel_flops = L * attn_core_flops(cfg, ctx.sum())
    kernel_bytes = L * (ctx.sum() * 2 * cfg.num_kv_heads * cfg.hd
                        + B * 2 * cfg.num_heads * cfg.hd) * BF16
    model = (L * (attn_flops(cfg, B, 0) + mlp_flops(cfg, B))
             + kernel_flops + logits_flops(cfg, B))
    return {"kernel_flops": kernel_flops, "kernel_bytes": kernel_bytes,
            "model_flops": model}


def prefill(cfg: Shape, width: int, base: int) -> dict:
    """A ``width``-token prefill behind ``base`` seated rows: every query
    row reads the whole prefix and, causally, the rows before it."""
    L, W = cfg.num_layers, float(width)
    pairs = W * base + W * (W + 1) / 2
    kernel_flops = L * attn_core_flops(cfg, pairs)
    kernel_bytes = L * ((2 * W * cfg.num_heads * cfg.hd)
                        + 2 * (W + base) * cfg.num_kv_heads * cfg.hd) * BF16
    model = (L * (attn_flops(cfg, W, 0) + mlp_flops(cfg, W))
             + kernel_flops + logits_flops(cfg, W))
    return {"kernel_flops": kernel_flops, "kernel_bytes": kernel_bytes,
            "model_flops": model}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
