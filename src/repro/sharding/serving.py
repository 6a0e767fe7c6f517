"""Mesh placement for the serving stack (engine caches, prefixes, kernels).

Training shards *parameters* from their recorded logical axes
(:mod:`repro.sharding.rules`); serving additionally has to place the
engine-owned state — dense per-slot KV stripes, paged block pools, block
tables, materialized compressed prefixes — none of which carries logical
axes.  This module derives those placements from the one invariant the
whole serving design preserves: **attention splits by head**.

* ``k``/``v`` (dense ``(slots, L, Hkv, hd)``, cross ``ck``/``cv``)
  shard the head axis on the mesh "model" axis, paged pools
  ``(N, bs, W)`` their lane axis by whole heads (where the heads fill the
  row), and replicate everything else — slots, positions and block
  structure are identical on every shard, so the host-side block tables
  and per-slot length vectors stay plain replicated numpy and the control
  plane never becomes mesh-aware.
* MLA ``lat`` latent rows have *no* head axis (that is the point of
  the absorbed decode) and stay replicated — at kv_lora_rank floats per
  token they are the cheap leaf.
* mamba ``conv``/``ssm`` recurrent state shards its channel/head dims
  like the corresponding weights (``mamba_inner`` / ``mamba_heads``).

Non-divisible dims drop to replication via :func:`repro.sharding.rules
.spec_for`, so a 3-head smoke config on a 2-way model mesh still runs —
it just replicates that leaf.

See docs/ARCHITECTURE.md §"Sharded serving".
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.rules import BASELINE_RULES, Rules, spec_for

__all__ = [
    "BASELINE_RULES", "cache_shardings", "constrain_cache",
    "constrain_heads", "leaf_sharding", "leaf_spec", "model_axis_size",
    "shard_cache", "shard_map_heads", "shard_map_replicated",
]

#: trailing logical dims per cache/prefix leaf key; leading dims (layer
#: stack, batch/pool, positions) are always replicated.  The same table
#: covers every layout the key appears in — dense cache, paged pool,
#: stacked period section, materialized prefix, batch-free store row —
#: because the head/channel axes are always the *trailing* ones.
_TRAILING = {
    "k": ("kv_heads", None),
    "v": ("kv_heads", None),
    "ck": ("heads", None),
    "cv": ("heads", None),
    "lat": (),
    "h": (),            # compressor output O^i: (B, m, d_model), replicated
    "conv": ("mamba_inner",),
    "ssm": ("mamba_heads", None, None),
}


def model_axis_size(mesh: Optional[Mesh]) -> int:
    """Extent of the tensor-parallel axis (1 when no mesh / no axis)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("model", 1))


def _leaf_key(path) -> Optional[str]:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return None


def leaf_spec(key: Optional[str], ndim: int, shape: Tuple[int, ...],
              mesh: Mesh, rules: Rules,
              pool_rows: Optional[Tuple[int, int]] = None) -> P:
    """PartitionSpec of one leaf.  ``pool_rows = (Hkv, hd)`` marks
    ``k``/``v`` as lane-merged paged pools whose rows hold ``Hkv`` heads
    of ``hd`` lanes (zero-padded to the 128-lane tile): their lane axis
    splits by whole heads where the heads divide the "model" axis and fill
    the row, and replicates otherwise."""
    if pool_rows is not None and key in ("k", "v"):
        heads, hd = pool_rows
        if heads % model_axis_size(mesh) or shape[-1] != heads * hd:
            return P()
        return spec_for(shape, (None,) * (ndim - 1) + ("kv_heads",), mesh,
                        rules)
    trailing = _TRAILING.get(key, ())
    if ndim < len(trailing):
        return P()
    logical = (None,) * (ndim - len(trailing)) + trailing
    return spec_for(shape, logical, mesh, rules)


def leaf_sharding(key: Optional[str], arr, mesh: Mesh,
                  rules: Rules = BASELINE_RULES) -> NamedSharding:
    """NamedSharding for one cache/prefix leaf by its dict key — the
    per-leaf form of :func:`cache_shardings`, used by the tiered store's
    promotion path to ``device_put`` each host chunk directly into the
    pool layout (no replicated detour, no second host round-trip)."""
    return NamedSharding(
        mesh, leaf_spec(key, arr.ndim, tuple(arr.shape), mesh, rules))


def cache_shardings(tree, mesh: Mesh, rules: Rules = BASELINE_RULES,
                    pool_rows: Optional[Tuple[int, int]] = None):
    """NamedSharding pytree for any Layerwise cache / prefix / store-row
    tree, keyed by leaf name (``k``/``v``/``ckv``/…).  Works for dense and
    paged layouts alike — the head axis is trailing in both; a paged
    cache passes ``pool_rows`` (its KV heads and head width), since its
    ``k``/``v`` pools keep the heads merged into the lane axis."""

    def one(path, x):
        return NamedSharding(
            mesh, leaf_spec(_leaf_key(path), x.ndim, x.shape, mesh, rules,
                            pool_rows))

    return jax.tree_util.tree_map_with_path(one, tree)


def shard_cache(tree, mesh: Optional[Mesh], rules: Rules = BASELINE_RULES,
                pool_rows: Optional[Tuple[int, int]] = None):
    """Place a cache/prefix tree on the mesh (no-op without a mesh)."""
    if mesh is None:
        return tree
    return jax.device_put(tree, cache_shardings(tree, mesh, rules,
                                                pool_rows))


def constrain_cache(tree, mesh: Optional[Mesh],
                    rules: Rules = BASELINE_RULES,
                    pool_rows: Optional[Tuple[int, int]] = None):
    """``with_sharding_constraint`` a cache/prefix tree inside jit — pins
    freshly materialized prefixes to the pool layout so the compile →
    store.put handoff never round-trips through a replicated gather."""
    if mesh is None or model_axis_size(mesh) <= 1:
        return tree

    def one(path, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, leaf_spec(_leaf_key(path), x.ndim,
                                             x.shape, mesh, rules,
                                             pool_rows)))

    return jax.tree_util.tree_map_with_path(one, tree)


def constrain_heads(x, mesh: Optional[Mesh], axis: int = 2):
    """Pin a (..., heads, hd) attention operand's head axis to the model
    mesh axis (replicating the rest) so GSPMD keeps decode head-parallel
    instead of gathering the cache.  No-op when no mesh / heads don't
    divide."""
    n = model_axis_size(mesh)
    if n <= 1 or x.ndim <= axis or x.shape[axis] % n:
        return x
    entries = [None] * x.ndim
    entries[axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))


def _shard_map(f, mesh: Mesh, in_specs, out_specs):
    # pallas_call has no replication rule — checking is pointless here
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_map_heads(f, mesh: Mesh, head_args, replicated_args: int,
                    head_axis: int = 2, out_ndims=4, head_axes=None):
    """Wrap a head-parallel kernel in shard_map: the first ``head_args``
    operands split their ``head_axis`` over "model" (batch, positions and
    block structure replicated), the remaining ``replicated_args``
    operands (positions, lengths, block tables) are replicated on every
    shard, and the outputs — ``out_ndims`` gives each one's rank, an int
    for a single output or a tuple — are head-split like the inputs.
    ``head_axes`` gives each head operand its own split axis (negative
    counts from the end: a lane-merged pool splits its last axis).

    This is what makes the *pallas* kernels mesh-runnable: unlike jnp ops
    they have no GSPMD partitioning rule, so each shard must run the
    kernel on its own head slice explicitly.
    """
    def head_spec(ndim, axis=head_axis):
        entries = [None] * ndim
        entries[axis] = "model"
        return P(*entries)

    def wrapped(*args):
        assert len(args) == head_args + replicated_args
        axes = head_axes or (head_axis,) * head_args
        in_specs = tuple(head_spec(a.ndim, ax) for a, ax in
                         zip(args[:head_args], axes)) + \
            tuple(P() for _ in args[head_args:])
        out_specs = (head_spec(out_ndims) if isinstance(out_ndims, int)
                     else tuple(head_spec(n) for n in out_ndims))
        return _shard_map(f, mesh, in_specs, out_specs)(*args)

    return wrapped


def shard_map_replicated(f, mesh: Mesh):
    """Run ``f`` whole on every device of ``mesh``, every operand and
    output replicated: the mesh path for a Pallas kernel whose operands
    cannot be split (a one-head cross-attention, heads that do not divide
    the "model" axis)."""
    def wrapped(*args):
        return _shard_map(f, mesh, tuple(P() for _ in args), P())(*args)

    return wrapped
