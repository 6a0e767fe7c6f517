"""Mixture-of-Experts with sort-based capacity dispatch.

No (tokens × experts × capacity) dispatch einsum: tokens are argsorted by
assigned expert, windowed into per-expert capacity buffers, pushed through a
grouped matmul (Pallas kernel on TPU, einsum oracle elsewhere), and
scatter-combined back.  Compiled FLOPs ≈ capacity_factor × ideal.

Expert weights are stacked on a leading "expert" logical axis → sharded on
the mesh "model" axis (expert parallelism).

Dispatch locality (``MoEConfig.dispatch_groups``): routing is per-token,
but the argsort/cumsum/scatter chain runs within G independent token
groups.  G = 1 is the classic global sort; with G = data-shard count the
whole dispatch carries a leading sharded group axis, so under GSPMD the
MoE layer partitions with *no token-stream gathers* — measured in
EXPERIMENTS.md §Perf (hillclimb 1: jamba train collective bytes).
Capacity is per-group (C = cf·Ng·k/E), so expected drop rates match the
global sort when tokens are shuffled across groups, which data-parallel
batching guarantees.  ``capacity_factor=None`` is dropless: every held
expert's buffer takes all Ng tokens (the serving engine runs so), and
the Pallas grouped matmul is handed each expert's row count, so it
computes the routed rows padded to its row tile, not the whole buffer.

Expert share (``MoEConfig.held_experts``): the router scores all
``num_experts`` and takes the top k; only pairs whose expert lies in this
device's share ``[expert_offset, expert_offset + held)`` are dispatched,
and the layer returns their part of the output plus the shared experts.
Summed over every share (shared experts counted once) that is the whole
layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import init_mlp, apply_mlp
from repro.models.param import ParamBuilder
from repro.sharding.ctx import moe_dispatch_plan


def init_moe(b: ParamBuilder, cfg: ModelConfig) -> None:
    m = cfg.moe
    d, E, F = cfg.d_model, m.held, m.expert_d_ff  # E: the experts held here
    eb = b.child("moe")
    eb.make("router", (d, m.num_experts), ("embed", "expert"))
    # expert weights use "embed_ep", NOT "embed": FSDP-sharding their
    # d_model dim makes every expert matmul contract over a sharded axis
    # — XLA then emits (E, C, F)-sized partial-sum all-reduces per MoE
    # layer plus token-stream permutes (measured: the dominant collective
    # in every MoE train/prefill cell; EXPERIMENTS.md §Perf hillclimb 1).
    # Experts shard on "model" (EP); their d_model dim stays unsharded.
    eb.make("wg", (E, d, F), ("expert", "embed_ep", "ff"), fan_in=d)
    eb.make("wi", (E, d, F), ("expert", "embed_ep", "ff"), fan_in=d)
    eb.make("wo", (E, F, d), ("expert", "ff", "embed_ep"), fan_in=F)
    if m.num_shared_experts:
        init_mlp(eb.child("shared"), cfg,
                 d_ff=m.num_shared_experts * m.shared_ff(), mlp_type="swiglu")


def _dropless_block(m, n_tokens: int) -> int:
    """Row tile of a dropless dispatch: a power of two covering twice an
    expert's even share (N·k/E rows), so most experts take one tile (and
    read their weights once), 8 to 256 rows."""
    share = -(-2 * n_tokens * m.top_k // m.num_experts)
    return max(8, min(256, 1 << max(0, share - 1).bit_length()))


def split_rows(rows, held: int):
    """Split the ``rows`` counts of :func:`apply_moe`, stacked over
    layers as ``(layers, held + 1 + N)``, into the pairs each held expert
    received ``(layers, held)``, the rows its grouped matmuls computed
    ``(layers,)``, and the held pairs of each token ``(layers, N)``."""
    return rows[:, :held], rows[:, held], rows[:, held + 1:]


def _capacity(m, n_tokens: int) -> int:
    if m.capacity_factor is None:  # dropless: an expert may take them all
        c = n_tokens
    else:
        c = int(m.capacity_factor * n_tokens * m.top_k / m.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def _dispatch(xf, ids, E: int, k: int, C: int):
    """(Ng, D) tokens + (Ng, k) expert ids -> (E, C, D) capacity buffers
    plus the metadata `_combine` needs.  An id of ``E`` names an expert
    not held here: its pair is never placed.  Pure per-group function:
    vmaps over a leading (sharded) group axis with zero cross-group
    traffic."""
    Ng, D = xf.shape
    flat_ids = ids.reshape(Ng * k)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(Ng * k, dtype=jnp.int32) - starts[sorted_ids]
    keep = (rank < C) & (sorted_ids < E)
    buf_idx = jnp.where(keep, sorted_ids * C + rank, E * C)  # OOB -> dropped
    token_idx = order // k
    buffers = jnp.zeros((E * C, D), xf.dtype).at[buf_idx].set(
        xf[token_idx], mode="drop").reshape(E, C, D)
    return buffers, (keep, buf_idx, order)


def _combine(y_buf, md, gates, k: int):
    """Inverse of `_dispatch`: (E, C, D) expert outputs -> (Ng, D)."""
    keep, buf_idx, order = md
    E, C, D = y_buf.shape
    flat = y_buf.reshape(E * C, D)
    y_sorted = jnp.where(keep[:, None],
                         flat.at[buf_idx].get(mode="fill", fill_value=0), 0)
    inv = jnp.argsort(order)
    Ng = gates.shape[0]
    y_k = y_sorted[inv].reshape(Ng, k, D)
    return jnp.sum(y_k * gates[..., None].astype(y_k.dtype), axis=1)


def _expert_ffn(p, buffers, impl, counts=None, block_c=None):
    """SwiGLU through the per-expert grouped matmul.  Accepts (E, C, D) or
    (G, E, C, D); the Pallas path folds G into C (one kernel launch).
    ``counts`` (E,) (one group only): the rows each expert received, the
    kernel computing those alone, in row tiles of ``block_c``."""
    def gmm(x, w):
        if x.ndim == 3:
            return ops.gmm(x, w, counts=counts, block_c=block_c, impl=impl)
        G, E, C, D = x.shape
        if impl == "pallas":
            x2 = x.transpose(1, 0, 2, 3).reshape(E, G * C, D)
            out = ops.gmm(x2, w, impl=impl)
            return out.reshape(E, G, C, -1).transpose(1, 0, 2, 3)
        return jnp.einsum("gecd,edf->gecf", x, w)

    h = jax.nn.silu(gmm(buffers, p["wg"])) * gmm(buffers, p["wi"])
    return gmm(h, p["wo"])


def apply_moe(p, cfg: ModelConfig, x, *, impl: str = "auto"):
    """x: (B, S, D) -> (y, aux_loss, rows).  ``rows`` (held + 1 + N,)
    int32 (:func:`split_rows`): the token-expert pairs routed to each held
    expert (dropped ones included), the rows the grouped matmuls computed,
    and each token's pairs with held experts."""
    m = cfg.moe
    B, S, D = x.shape
    E, k, Eh = m.num_experts, m.top_k, m.held
    N = B * S
    # Under a distributed residual constraint, reshard tokens batch-only
    # and group the dispatch by batch shard (ctx.moe_dispatch_plan); the
    # config's dispatch_groups is the single-host/test override.
    x, auto_groups = moe_dispatch_plan(x, E)
    G = auto_groups or m.dispatch_groups
    if G <= 0 or N % G:
        G = 1
    xf = x.reshape(N, D)

    with jax.named_scope("moe.route"):
        logits = (xf @ p["router"]).astype(jnp.float32)  # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)  # (N, k)
        if m.norm_topk_prob:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        # load-balance aux loss (Switch-style, global)
        me = probs.mean(axis=0)  # (E,)
        ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (N * k)
        aux = m.aux_loss_weight * E * jnp.sum(me * ce)

        if Eh != E or m.expert_offset:
            # this device's share: other experts' pairs become id Eh
            local = ids - m.expert_offset
            ids = jnp.where((local >= 0) & (local < Eh), local, Eh)
        per_expert = jnp.zeros((Eh,), jnp.int32).at[ids.reshape(-1)].add(
            1, mode="drop")
        per_token = jnp.sum(ids < Eh, axis=-1, dtype=jnp.int32)

    Ng = N // G
    C = _capacity(m, Ng)
    kernel = ops.gmm_uses_kernel(impl)
    if G == 1 and kernel and m.capacity_factor is None:
        # dropless: the kernel computes each expert's rows in whole tiles
        bc = min(_dropless_block(m, Ng), C)
        C = -(-C // bc) * bc
        counts = per_expert
        computed = jnp.sum(-(-per_expert // bc) * bc, dtype=jnp.int32)
    else:  # every buffer row (the kernel's whole row tiles)
        bc = counts = None
        rows_c = ops.gmm_rows(G * C) if kernel else G * C
        computed = jnp.int32(Eh * rows_c)
    rows = jnp.concatenate([per_expert, computed[None], per_token])

    if G == 1:
        with jax.named_scope("moe.route"):
            buffers, md = _dispatch(xf, ids, Eh, k, C)
        with jax.named_scope("moe.experts"):
            y_buf = _expert_ffn(p, buffers, impl, counts, bc)
        with jax.named_scope("moe.combine"):
            y = _combine(y_buf, md, gates, k)
    else:
        with jax.named_scope("moe.route"):
            buffers, md = jax.vmap(lambda a, b: _dispatch(a, b, Eh, k, C))(
                xf.reshape(G, Ng, D), ids.reshape(G, Ng, k))
        with jax.named_scope("moe.experts"):
            y_buf = _expert_ffn(p, buffers, impl)  # (G, E, C, D) in one call
        with jax.named_scope("moe.combine"):
            y = jax.vmap(lambda yb, m_, g: _combine(yb, m_, g, k))(
                y_buf, md, gates.reshape(G, Ng, k)).reshape(N, D)

    if m.num_shared_experts:
        with jax.named_scope("moe.experts"):
            shared = apply_mlp(p["shared"]["mlp"], cfg, xf, mlp_type="swiglu")
        with jax.named_scope("moe.combine"):
            y = y + shared
    # the residual stream must stay in the model dtype: a float32 leak
    # here upcasts every downstream activation (2× memory + collective
    # bytes on all MoE archs — caught in the §Perf autopsy)
    return y.reshape(B, S, D).astype(x.dtype), aux, rows
