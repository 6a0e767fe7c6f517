"""Logical-axis → mesh-axis sharding rules (MaxText-style).

Params record logical axes at init (:mod:`repro.models.param`); this module
turns the logical tree + a rules table + a mesh into NamedShardings.
Axes whose dimension does not divide the assigned mesh-axis extent are
dropped to replication (e.g. granite's 40 experts or its 49155-row vocab
on a 16-way model axis) — dimension-safe by construction.

Two built-in rule sets:

* BASELINE_RULES — pure tensor/expert parallel weights ("model" axis),
  replicated across data: the paper's own 512-chip DP posture.
* FSDP_RULES     — additionally shards every kernel's "embed" dim over
  the data axes (ZeRO-3-style fully-sharded weights; XLA all-gathers a
  layer at a time inside the scan).  Required to fit the 236B/398B
  configs.  LAYERS_FSDP_RULES shards the stacked-layer dim instead
  (only useful when repeats % data_axes == 0).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils.pytree import tree_flatten_with_names

AxisAssignment = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, AxisAssignment]

# "data_axes" is resolved per-mesh: ("pod", "data") when a pod axis exists.
BASELINE_RULES: Rules = {
    "vocab": "model",
    "embed": None,
    "embed_ep": None,  # expert-weight d_model: never FSDP-sharded (the
    # expert matmul contracts it; sharding it trades a cheap weight
    # gather for per-layer partial-sum all-reduces — §Perf hillclimb 1)
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "expert": "model",
    "mamba_inner": "model",
    "mamba_heads": "model",
    "mla_lora": None,
    "layers": None,
}

FSDP_RULES: Rules = dict(BASELINE_RULES, embed="data_axes")
LAYERS_FSDP_RULES: Rules = dict(BASELINE_RULES, layers="data_axes")
# pre-fix posture (expert weights FSDP-sharded on d_model) — kept for the
# §Perf before/after measurement
FSDP_EP_EMBED_RULES: Rules = dict(FSDP_RULES, embed_ep="data_axes")


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _resolve(assign: AxisAssignment, mesh: Mesh) -> Tuple[str, ...]:
    if assign is None:
        return ()
    if assign == "data_axes":
        return _data_axes(mesh)
    if isinstance(assign, str):
        return (assign,)
    return tuple(assign)


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def spec_for(shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
             mesh: Mesh, rules: Rules) -> P:
    entries = []
    used = set()
    for dim, name in zip(shape, logical):
        assign = _resolve(rules.get(name), mesh) if name else ()
        # an axis may be consumed only once per spec; drop non-divisible
        assign = tuple(a for a in assign if a not in used)
        if assign and dim % _axes_size(mesh, assign) == 0:
            entries.append(assign if len(assign) > 1 else assign[0])
            used.update(assign)
        else:
            entries.append(None)
    return P(*entries)


def _flatten_axes(axes_tree):
    """Flatten the logical-axes tree keeping each axis *tuple* as one leaf
    (tuples are pytree nodes, so the default flatten would explode them)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    out = {}
    for path, leaf in flat:
        parts = []
        for p in path:
            parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
        out["/".join(parts)] = leaf
    return out


def logical_to_shardings(abstract_params, axes_tree, mesh: Mesh, rules: Rules):
    """Pytree of NamedSharding matching params structure."""
    flat_p = tree_flatten_with_names(abstract_params)
    flat_a = _flatten_axes(axes_tree)
    leaves, treedef = jax.tree.flatten(abstract_params)
    out = []
    for (name, leaf) in flat_p:
        logical = flat_a[name]
        out.append(NamedSharding(mesh, spec_for(leaf.shape, logical, mesh, rules)))
    return jax.tree.unflatten(treedef, out)


def init_sharded(init_fn, axes_tree, mesh: Mesh, rules: Rules, *args):
    """Run ``init_fn(*args)`` under jit with every output leaf placed from
    its logical axes: parameters are created on their shards and never
    gathered on one device first (a model too large for one chip can
    only be built this way)."""
    abstract = jax.eval_shape(init_fn, *args)
    shardings = logical_to_shardings(abstract, axes_tree, mesh, rules)
    return jax.jit(init_fn, out_shardings=shardings)(*args)


def batch_sharding(mesh: Mesh, ndim: int = 2, batch_dim: int = 0):
    """Shard the batch dim over (pod, data); replicate the rest."""
    entries = [None] * ndim
    entries[batch_dim] = _data_axes(mesh)
    return NamedSharding(mesh, P(*entries))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def opt_state_shardings(state_abstract, param_shardings, mesh: Mesh):
    """Optimizer state entries inherit their param's sharding by name.

    AdamW state is flat-dict-keyed by the param path with '/'-separators;
    mu/nu/master have the same shape as the param.
    """
    flat_ps = dict(tree_flatten_with_names(param_shardings))

    def lookup(kind_tree):
        out = {}
        for name, leaf in kind_tree.items():
            sh = flat_ps.get(name)
            out[name] = sh if sh is not None else replicated(mesh)
        return out

    return {
        "mu": lookup(state_abstract["mu"]),
        "nu": lookup(state_abstract["nu"]),
        "master": lookup(state_abstract["master"]),
        "count": replicated(mesh),
    }
