"""Sharding-context helpers: no-op guarantees off-mesh, ablation switch."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding import ctx


def test_constrain_noop_without_context(rng):
    x = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
    assert ctx.constrain(x) is x
    assert ctx.head_sharded(jnp.zeros((1, 2, 4, 8))) is not None


def test_moe_plan_noop_without_context(rng):
    x = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
    out, groups = ctx.moe_dispatch_plan(x)
    assert out is x and groups is None


def test_moe_plan_disabled_switch():
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", "model", None))
    x = jnp.zeros((4, 4, 8))
    with ctx.act_sharding(sh):
        with ctx.moe_plan_disabled():
            out, groups = ctx.moe_dispatch_plan(x)
            assert out is x and groups is None
    # context restored
    out, groups = ctx.moe_dispatch_plan(x)
    assert out is x and groups is None


def test_act_sharding_context_restores():
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None, None))
    x = jnp.zeros((2, 4, 8))
    with ctx.act_sharding(sh):
        # eager with_sharding_constraint may return its input unchanged on a
        # trivial mesh, so check the traced program instead of object identity
        jaxpr = str(jax.make_jaxpr(ctx.constrain)(x))
        assert "sharding_constraint" in jaxpr  # constraint applied
        np.testing.assert_array_equal(np.asarray(ctx.constrain(x)),
                                      np.asarray(x))
    assert ctx.constrain(x) is x  # restored


def test_constrain_skips_mismatched_rank():
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None, None))
    with ctx.act_sharding(sh):
        x2d = jnp.zeros((2, 4))
        assert ctx.constrain(x2d) is x2d
