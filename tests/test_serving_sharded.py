"""Tensor-parallel serving: sharded-vs-single-device parity.

All multi-device tests run in a subprocess with a forced host-platform
device topology (the device count locks at the first jax import — see
tests/test_pipeline.py for the same pattern).  One subprocess covers the
whole matrix: the reference single-device engine and the 2-/4-way model
meshes all live on the same forced 4-device host, so the comparison is
apples-to-apples down to the compiled partitioning.

Covered:

* ``ops.decode_attention`` / ``ops.paged_decode_attention`` parity
  (<= 1e-4) for the jnp path under GSPMD and the pallas path under
  ``shard_map`` (interpret mode), heads split 2- and 4-way;
* dense and paged ``ServingEngine`` greedy serving: token-identical to
  the single-device engine on 2- and 4-way model meshes, offline
  prefixes seated per slot;
* online-compiled prefixes (raw_shots through the ``PrefixCompiler``):
  token-identical sharded vs single-device, dense and paged.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core import memcom
    from repro.kernels import ops
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as tfm
    from repro.serving import Request
    from repro.serving.engine import ServingEngine, materialize_prefix

    report = {}
    rng = np.random.default_rng(0)

    # ---- ops parity: jnp (GSPMD) and pallas (shard_map) decode paths ----
    B, S, Hq, Hkv, D, L = 3, 1, 8, 4, 16, 32
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hkv, D)), jnp.float32)
    lengths = jnp.asarray([9, 17, 32], jnp.int32)
    ref = ops.decode_attention(q, k, v, lengths=lengths, impl="dense")
    bs, nb = 4, 8
    pk = jnp.asarray(rng.standard_normal((1 + B * nb, bs, Hkv, D)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((1 + B * nb, bs, Hkv, D)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
    pref = ops.paged_decode_attention(q, pk, pv, block_tables=tables,
                                      lengths=lengths, impl="dense")
    for model in (2, 4):
        mesh = make_serving_mesh(model=model)
        out = ops.decode_attention(q, k, v, lengths=lengths,
                                   impl="pallas", mesh=mesh)
        report[f"dense_pallas_{model}"] = float(jnp.abs(out - ref).max())
        out = jax.jit(lambda q, k, v, l: ops.decode_attention(
            q, k, v, lengths=l, impl="jnp", mesh=mesh))(q, k, v, lengths)
        report[f"dense_jnp_{model}"] = float(jnp.abs(out - ref).max())
        out = ops.paged_decode_attention(q, pk, pv, block_tables=tables,
                                         lengths=lengths, impl="pallas",
                                         mesh=mesh)
        report[f"paged_pallas_{model}"] = float(jnp.abs(out - pref).max())
        out = jax.jit(lambda q, k, v, t, l: ops.paged_decode_attention(
            q, k, v, block_tables=t, lengths=l, impl="jnp", mesh=mesh))(
            q, pk, pv, tables, lengths)
        report[f"paged_jnp_{model}"] = float(jnp.abs(out - pref).max())

    # ---- prefill / compression kernels on a mesh: Mosaic calls cannot be
    # auto-partitioned, so they run per device (by head, or replicated) ----
    Sq, Sk = 24, 40
    qs = jnp.asarray(rng.standard_normal((2, Sq, Hq, D)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((2, Sk, Hkv, D)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((2, Sk, Hkv, D)), jnp.float32)
    kp3 = jnp.asarray(rng.standard_normal((2, 16, Hkv, D)), jnp.float32)
    xq = jnp.asarray(rng.standard_normal((1, 8, 32)), jnp.float32)
    xk = jnp.asarray(rng.standard_normal((1, 40, 32)), jnp.float32)
    want_causal = ops.self_attention_causal(qs, ks[:, :Sq], vs[:, :Sq],
                                            impl="dense")
    want_prefix = ops.attention_with_prefix(qs, ks[:, :Sq], vs[:, :Sq], kp3,
                                            kp3, impl="jnp")
    want_x = ops.memcom_xattn(xq, xk, xk, impl="dense")
    for model in (2, 4):
        mesh = make_serving_mesh(model=model)
        for name, fn, want in (
                ("causal", lambda: ops.self_attention_causal(
                    qs, ks[:, :Sq], vs[:, :Sq], impl="pallas", mesh=mesh),
                 want_causal),
                ("prefix", lambda: ops.attention_with_prefix(
                    qs, ks[:, :Sq], vs[:, :Sq], kp3, kp3, impl="pallas",
                    mesh=mesh), want_prefix),
                ("xattn", lambda: ops.memcom_xattn(
                    xq, xk, xk, impl="pallas", mesh=mesh), want_x)):
            out = jax.jit(fn)()
            report[f"{name}_pallas_{model}"] = float(jnp.abs(out - want).max())

    # ---- engine parity: offline prefixes, dense + paged ----
    cfg = get_smoke_config("smollm-135m").replace(
        d_model=128, num_heads=8, num_kv_heads=4, d_ff=256)
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    shots = jnp.asarray(rng.integers(4, cfg.vocab_size, (1, 40)), jnp.int32)
    kv = materialize_prefix(params, cfg, memcom.compress(mc, cfg, shots)[0])
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9)]

    def serve_offline(eng):
        reqs = [Request(tokens=p, max_new=4, prefix="task") for p in prompts]
        out = eng.serve(reqs)
        return [out[r.uid].tolist() for r in reqs]  # request order, not uid

    for layout, kw in (("dense", {}),
                       ("paged", dict(kv_layout="paged", block_size=4))):
        eng = ServingEngine(cfg, params, slots=2, max_len=64, **kw)
        eng.add_prefix("task", kv)
        want = serve_offline(eng)
        for model in (2, 4):
            mesh = make_serving_mesh(model=model)
            eng = ServingEngine(cfg, params, slots=2, max_len=64,
                                mesh=mesh, **kw)
            eng.add_prefix("task", kv)
            report[f"engine_{layout}_{model}"] = (serve_offline(eng) == want)

    # ---- engine parity: online-compiled prefixes (raw_shots) ----
    raw = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    online = [Request(tokens=p, max_new=3, raw_shots=raw) for p in prompts]

    def serve_online(eng):
        out = eng.serve(online)
        return [out[r.uid].tolist() for r in online]

    for layout, kw in (("dense", {}),
                       ("paged", dict(kv_layout="paged", block_size=4))):
        want = serve_online(ServingEngine(
            cfg, params, slots=2, max_len=96, compressor=mc,
            compile_token_budget=16, **kw))
        mesh = make_serving_mesh(model=2)
        got = serve_online(ServingEngine(
            cfg, params, slots=2, max_len=96, compressor=mc,
            compile_token_budget=16, mesh=mesh, **kw))
        report[f"online_{layout}_2"] = (got == want)

    print(json.dumps(report))
""")


@pytest.mark.slow
def test_sharded_serving_parity(tmp_path):
    """2-/4-way model-mesh serving == single device: kernel-level parity
    <= 1e-4, engine-level greedy tokens identical (offline and online-
    compiled prefixes, dense and paged layouts)."""
    script = tmp_path / "sharded_parity.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=1800, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    report = json.loads(res.stdout.strip().splitlines()[-1])
    for key, val in report.items():
        if isinstance(val, bool):
            assert val, f"{key}: sharded tokens differ from single-device"
        else:
            assert val <= 1e-4, f"{key}: parity error {val}"


def test_make_serving_mesh_single_device():
    """A 1x1 serving mesh works on the plain single-CPU test process (the
    mesh-aware engine path must not require forced topologies)."""
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as tfm
    from repro.serving import Request
    from repro.serving.engine import ServingEngine

    mesh = make_serving_mesh(model=1)
    assert dict((n, int(mesh.shape[n])) for n in mesh.axis_names) == \
        {"data": 1, "model": 1}
    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    ref = ServingEngine(cfg, params, slots=1, max_len=16).serve(
        [Request(tokens=prompt, max_new=3)])
    eng = ServingEngine(cfg, params, slots=1, max_len=16, mesh=mesh)
    out = eng.serve([Request(tokens=prompt, max_new=3)])
    assert [v.tolist() for v in out.values()] == \
        [v.tolist() for v in ref.values()]
    assert eng.stats()["mesh"] == {"data": 1, "model": 1}


def test_make_serving_mesh_too_many_devices():
    from repro.launch.mesh import make_serving_mesh

    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_serving_mesh(model=4096)


def test_rules_without_mesh_rejected():
    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    from repro.serving.engine import ServingEngine
    from repro.sharding.rules import BASELINE_RULES

    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    with pytest.raises(ValueError, match="rules given without a mesh"):
        ServingEngine(cfg, params, slots=1, max_len=16,
                      rules=BASELINE_RULES)
