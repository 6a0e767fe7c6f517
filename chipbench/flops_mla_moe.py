"""Operations and bytes of DeepSeek-V2's latent decode attention and of
its grouped expert matmuls, from a configuration file (Hugging Face key
names).  :mod:`chipbench.flops` describes a dense GQA block; these read
the latent and expert widths instead.  A matmul (m x k)(k x n) costs 2mkn
operations; bytes are what a kernel must read and write at least, in
bfloat16.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

BF16 = 2


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    rank: int        # kv_lora_rank: the latent value's lanes
    latent: int      # kv_lora_rank + qk_rope_head_dim: the latent key's
    layers: int      # MLA layers (all of them)
    expert_f: int    # moe_intermediate_size

    @staticmethod
    def of(c: dict) -> "Dims":
        return Dims(c["hidden_size"], c["num_attention_heads"],
                    c["kv_lora_rank"],
                    c["kv_lora_rank"] + c["qk_rope_head_dim"],
                    c["num_hidden_layers"], c["moe_intermediate_size"])


def latent_decode(dims: Dims, ctx) -> dict:
    """One absorbed-MLA decode step over every layer, each slot reading
    ``ctx`` latent rows (its prefix and everything after, the new token
    included): every head scores each row's ``latent`` lanes and sums its
    ``rank`` value lanes; the rows are read once for all heads (the lanes
    used, not the pool's zero pad), with each slot's query and output
    rows."""
    ctx = np.asarray(ctx, np.float64)
    B, L, H = len(ctx), dims.layers, dims.heads
    flops = L * 2 * H * ctx.sum() * (dims.latent + dims.rank)
    nbytes = L * (ctx.sum() * dims.latent
                  + B * H * (dims.latent + dims.rank)) * BF16
    return {"flops": flops, "bytes": nbytes}


def expert_gmm(dims: Dims, routed_rows: float, expert_hits: float) -> dict:
    """The grouped expert matmuls (gate, up, down) for ``routed_rows``
    token-expert pairs: 6 d F operations a pair; the weights of each
    (layer, held expert) that got a row (``expert_hits``) read once, and
    each pair's row in and out."""
    d, f = dims.d, dims.expert_f
    flops = 6.0 * d * f * routed_rows
    nbytes = (3.0 * d * f * expert_hits + 2.0 * d * routed_rows) * BF16
    return {"flops": flops, "bytes": nbytes}


def expert_matmul_labels(c: dict) -> dict:
    """How the grouped expert matmuls show in the device trace: the
    ``expert_gmm`` kernels, and the ops that fetch one layer's held expert
    weights out of the layer stack for them (the kernel then reads them
    from VMEM), which end in that weight's shape."""
    d, f, held = c["hidden_size"], c["moe_intermediate_size"], \
        c["n_routed_experts"]
    return {"expert_gmm": ("expert_gmm", f"= bf16[{held},{d},{f}]",
                           f"= bf16[{held},{f},{d}]")}


def moe_labels(c: dict) -> dict:
    """How the MoE layers' ops show in the device trace, whose op labels
    hold each op's HLO text (not its scope): the expert kernels and every
    op that reads a MoE weight — router, held experts, shared experts —
    named by that weight's shape, per layer or stacked."""
    d, f, held = c["hidden_size"], c["moe_intermediate_size"], \
        c["n_routed_experts"]
    sf, e = c["n_shared_experts"] * f, c["published"]["n_routed_experts"]
    return {"moe": ("expert_gmm", f"{held},{d},{f}]", f"{held},{f},{d}]",
                    f"{d},{sf}]", f"{sf},{d}]", f"{d},{e}]")}


# ops that contain others (a scan's while loop spans its body's ops, and
# its label lists every operand of the loop, weights included)
_CONTAINERS = (" while(", " conditional(", " call(")


def device_seconds(trace, table: dict) -> float:
    """Device seconds of the traced operations whose label one of
    ``table``'s substrings names (:func:`chipbench.xplane.kind_of`),
    averaged over the chips; ops that contain other ops are not counted,
    their contents are."""
    from chipbench import xplane

    tot = sum(o.end - o.start for c in trace.chips for o in c.ops
              if xplane.kind_of(o.label, table)
              and not any(k in o.label for k in _CONTAINERS))
    return tot / max(1, len(trace.chips)) / 1e9
