"""Data pipeline + sharding-rules unit tests, and a mini end-to-end
sharded lower/compile on an 8-device placeholder topology (subprocess,
so the main test process keeps its single real device)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.data import (
    ICLTaskSpec, Prefetcher, PretrainStream, SyntheticVocab,
    build_manyshot_prompt, make_episode,
)
from repro.data.pipeline import host_slice
from repro.sharding.rules import BASELINE_RULES, FSDP_RULES, spec_for

REPO_ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_prompt_builder_budget_and_balance(rng):
    v = SyntheticVocab(num_keys=32, num_labels=8)
    task = ICLTaskSpec(vocab=v, num_labels=8, keys_per_label=4)
    ep = make_episode(task, rng)
    budget = 65
    prompt = build_manyshot_prompt(task, ep, rng, budget)
    assert len(prompt) <= budget
    # class balance: round-robin ⇒ per-label shot counts differ by ≤ 1
    labels = prompt[3::4] - v.label_base
    counts = np.bincount(labels, minlength=8)
    assert counts.max() - counts.min() <= 1
    # structure: [SEP key ARROW label] repeated
    assert (prompt[0::4] == v.SEP).all()
    assert (prompt[2::4] == v.ARROW).all()


def test_prompt_budget_monotone(rng):
    """Fewer-shots baseline: smaller budget ⇒ prefix of the shot sequence
    (same construction, same RNG), the paper's §5 baseline definition."""
    v = SyntheticVocab(num_keys=32, num_labels=8)
    task = ICLTaskSpec(vocab=v, num_labels=8, keys_per_label=4)
    ep = make_episode(task, rng)
    big = build_manyshot_prompt(task, ep, np.random.default_rng(5), 64)
    small = build_manyshot_prompt(task, ep, np.random.default_rng(5), 32)
    assert len(small) <= 32 < len(big) <= 64
    np.testing.assert_array_equal(big[: len(small)], small)


def test_stream_source_target_split():
    s = PretrainStream(SyntheticVocab(), batch=3, seq_len=64,
                       split_choices=(40, 48), seed=1)
    b = s.batch_at(0)
    assert b["source"].shape[1] + b["target"].shape[1] == 64
    assert b["source"].shape[1] in (40, 48)


def test_prefetcher_orders_and_stops():
    seen = []
    pf = Prefetcher(lambda i: {"i": i}, start_step=5, depth=2)
    for _ in range(4):
        step, item = pf.get()
        seen.append(step)
        assert item["i"] == step
    pf.stop()
    assert seen == [5, 6, 7, 8]


def test_host_slice_partitions():
    sl = [host_slice(32, h, 4) for h in range(4)]
    idx = np.arange(32)
    got = np.concatenate([idx[s] for s in sl])
    np.testing.assert_array_equal(got, idx)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


class _StubMesh:
    """spec_for only reads axis_names and shape — a stub stands in for the
    production 16×16 mesh without needing 256 devices."""

    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_spec_for_divisibility():
    mesh = _StubMesh()
    # divisible → sharded; non-divisible → dropped to replication
    spec = spec_for((32, 64), ("vocab", "embed"), mesh, BASELINE_RULES)
    assert spec == P("model", None)
    spec = spec_for((17, 64), ("vocab", "embed"), mesh, BASELINE_RULES)
    assert spec == P(None, None)


def test_spec_for_no_axis_reuse():
    mesh = _StubMesh()
    spec = spec_for((32, 32), ("heads", "ff"), mesh, BASELINE_RULES)
    # both want "model"; only the first may take it
    assert spec == P("model", None)


def test_fsdp_rules_shard_embed_over_data():
    mesh = _StubMesh()
    spec = spec_for((32, 32), ("embed", "heads"), mesh, FSDP_RULES)
    # newer jax canonicalizes singleton axis tuples to bare names
    assert spec in (P(("data",), "model"), P("data", "model"))


def test_granite_oddballs_drop_to_replication():
    """granite: 40 experts and 49155-row vocab don't divide 16 — the
    rules must degrade those dims to replication, not crash."""
    mesh = _StubMesh()
    spec = spec_for((40, 1536, 512), ("expert", "embed", "ff"), mesh,
                    FSDP_RULES)
    assert spec in (P(None, ("data",), "model"), P(None, "data", "model"))
    spec = spec_for((49155, 1536), ("vocab", "embed"), mesh, FSDP_RULES)
    assert spec in (P(None, ("data",)), P(None, "data"))


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_smoke_config
    from repro.launch.steps import (build_memcom_train_step, memcom_shardings,
                                    param_shardings, _with_shardings,
                                    act_sharding_for, opt_shardings)
    from repro.core import memcom
    from repro.launch.mesh import make_mesh
    from repro.optim import AdamW
    from repro.sharding.ctx import act_sharding
    import jax.numpy as jnp

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config("smollm-135m").replace(
        d_model=128, num_heads=4, num_kv_heads=2, d_ff=256)
    step, _ = build_memcom_train_step(cfg, phase=1)
    mc_sh, mc_abs = memcom_shardings(cfg, mesh)
    tgt_sh, tgt_abs = param_shardings(cfg, mesh)
    mask = memcom.trainable_mask(mc_abs, 1)
    opt_abs = jax.eval_shape(AdamW(lr=0.0, mask=mask).init, mc_abs)
    opt_sh = opt_shardings(opt_abs, mc_sh, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = {
        "source": jax.ShapeDtypeStruct((8, 32), jnp.int32,
            sharding=NamedSharding(mesh, P("data", None))),
        "target": jax.ShapeDtypeStruct((8, 16), jnp.int32,
            sharding=NamedSharding(mesh, P("data", None))),
    }
    args = (_with_shardings(mc_abs, mc_sh), _with_shardings(opt_abs, opt_sh),
            _with_shardings(tgt_abs, tgt_sh), batch)
    with act_sharding(act_sharding_for(mesh, cfg, 8, 32)):
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    ca = compiled.cost_analysis()
    print(json.dumps({"ok": True, "flops": float(ca.get("flops", -1))}))
""")


@pytest.mark.slow
def test_sharded_memcom_train_compiles_8dev(tmp_path):
    """End-to-end: the MemCom Phase-1 train step lowers + compiles SPMD
    on an 8-device (4 data × 2 model) placeholder mesh."""
    script = tmp_path / "mini_dryrun.py"
    script.write_text(MINI_DRYRUN)
    res = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=900, env={**os.environ, "PYTHONPATH": "src"},
        cwd=REPO_ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["flops"] != 0
