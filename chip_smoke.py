"""On-chip smoke test of the MemCom compress -> serve path.

    python chip_smoke.py              # one TPU chip, smollm-360m
    python chip_smoke.py --chips 4    # 1x4 tensor-parallel gemma2-2b

One chip (the default) drives the main path at smollm-360m's published
widths (32 layers, d_model 960, 15/5 heads of 64, the 49,152-id
vocabulary, bfloat16, m = 512 memory slots) with random weights made
from ``--seed``:

1. device check — JAX must find a TPU; a failed TPU start is an error,
   never a CPU run (``JAX_PLATFORMS=tpu`` is set before jax is imported);
2. kernel parity — flash attention (prefill, prefix + lse), decode
   over dense stripes and over a paged pool, and the MemCom
   cross-attention against ``repro.kernels.ref`` in float32 and
   bfloat16;
3. offline path — ``memcom.compress`` of one 3,072-token many-shot
   prompt (6x into 512 slots), ``materialize_prefix``, then
   ``ServingEngine.serve`` of 8 ragged requests over 4 slots, dense
   KV layout;
4. online path — the same requests carrying their raw shots, compiled
   inside the serving loop in 512-token chunks, paged KV layout.

Both serving paths must complete every request with its ``max_new``
tokens, give finite decode logits, and run step programs that contain
Pallas kernels (``tpu_custom_call``).

5. launcher — ``repro.launch.serve.main`` at the same published widths
   (no ``--smoke``): two 3,072-token tasks compressed offline, 8
   requests served over 4 slots.

``--chips 4`` runs only the tensor-parallel phase: gemma2-2b (the
paper's target; target plus compressor do not fit one 16 GB chip) is
created sharded over a 1x4 mesh, compresses and serves there.  The bf16
logits of the last prefill row and the first decode step are compared
with the same target on one chip fed the same materialized prefix, both
measured against a float32 one-chip reference at highest matmul
precision; and the mesh compressor's prefix with the one the compressor
makes alone on one chip.

Every line but the last is a human-readable report.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``; any
failed phase raises, so the exit code is non-zero and no such line is
printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time  # reprolint: ignore-file[wall-clock] -- reports compile/serve seconds on the chip
from importlib import metadata
from pathlib import Path

HERE = Path(__file__).resolve().parent

# stated tolerances (max absolute error against the float32 oracle)
KERNEL_TOL = {"float32": 1e-4, "bfloat16": 1e-2}
# 1x4 mesh vs one chip, bf16 logits of the last prefill row and the first
# decode step: the mesh's max |error| against a float32 one-chip reference
# may be at most this multiple of the one-chip bf16 run's own max |error|
# (TP all-reduces round partial sums to bf16, so the two bf16 runs differ
# by up to the sum of their errors).  On four virtual CPU devices at a toy
# gemma2 size a correct mesh gave ratios of 1.02-1.03, and planted faults
# (two head shards of one layer's output projection swapped, KV shards
# rotated across devices, one device's kernel output dropped) gave 26-107
TP_BF16_ERR_FACTOR = 2.0
# the same logits on one chip, fed the prefix the compressor makes alone
# on one chip instead of the mesh's: they may move by at most this
# multiple of the one-chip bf16 error (two bf16 compressions round
# differently; toy size: 1.44-1.86 correct, 37-110 with every layer's
# kernels at fault, 1.16-4.06 with one layer's shards swapped)
COMPRESS_ERR_FACTOR = 4.0

PROMPT_TOKENS = 3072
SLOTS = 4
REQUESTS = 8
COMPILE_BUDGET = 512
BLOCK_SIZE = 16


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def device_check(jax, chips: int):
    devices = jax.devices()
    d0 = devices[0]
    check(d0.platform == "tpu", f"first device is {d0.platform!r}, not tpu")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} TPU devices, found {len(devices)}")
    say(f"jax {jax.__version__}, jaxlib {metadata.version('jaxlib')}, "
        f"libtpu {metadata.version('libtpu')}")
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def kernel_parity(jax, jnp, np, seed: int):
    """Each main-path kernel on the chip against the float32 oracle, at
    smollm-360m's attention widths (15 query / 5 KV heads of 64, d_model
    960, m = 512, t = 3072)."""
    from repro.kernels import ops, ref

    Hq, Hkv, D, d_model, m = 15, 5, 64, 960, 512
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return np.asarray(rng.standard_normal(shape) * 0.5, np.float32)

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(*[jnp.asarray(a, jnp.float32)
                                   for a in args]), np.float32)

    cases = []
    # prefill: causal self-attention over a 3k-token shot set
    S = PROMPT_TOKENS
    qkv = (rand(1, S, Hq, D), rand(1, S, Hkv, D), rand(1, S, Hkv, D))
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (1, S))
    cases.append((
        f"flash prefill S={S}", qkv,
        lambda q, k, v: ops.self_attention_causal(q, k, v, impl="pallas"),
        lambda q, k, v: ref.attention_ref(q, k, v, q_pos=pos, kv_pos=pos)))
    # prefill behind a compressed prefix (engine admission), lse merge
    Sp = 24
    pre = (rand(2, Sp, Hq, D), rand(2, Sp, Hkv, D), rand(2, Sp, Hkv, D),
           rand(2, m, Hkv, D), rand(2, m, Hkv, D))
    kv_pos = np.broadcast_to(np.arange(m + Sp, dtype=np.int32), (2, m + Sp))
    q_pos = kv_pos[:, m:]
    cases.append((
        "flash prefix+self B=2 S=24", pre,
        lambda q, k, v, kp, vp: ops.attention_with_prefix(
            q, k, v, kp, vp, impl="pallas"),
        lambda q, k, v, kp, vp: ref.attention_ref(
            q, jnp.concatenate([kp, k], 1), jnp.concatenate([vp, v], 1),
            q_pos=q_pos, kv_pos=kv_pos)))
    # dense-layout decode: 8 slots at ragged lengths in 576-row stripes,
    # read by the paged kernel as blocks of 288 rows
    B, L = 8, m + 64
    lengths = np.asarray([1, 9, 300, 512, 513, 530, 560, 576], np.int32)
    dec = (rand(B, 1, Hq, D), rand(B, L, Hkv, D), rand(B, L, Hkv, D))
    cases.append((
        "dense decode B=8 L=576", dec,
        lambda q, k, v: ops.decode_attention(q, k, v, lengths=lengths,
                                             impl="pallas"),
        lambda q, k, v: ops.decode_attention(q, k, v, lengths=lengths,
                                             impl="dense")))
    # paged decode: the same slots over a shuffled block pool
    nb = L // BLOCK_SIZE
    tables = (1 + rng.permutation(B * nb)).reshape(B, nb).astype(np.int32)
    pools = (rand(1 + B * nb, BLOCK_SIZE, Hkv, D),
             rand(1 + B * nb, BLOCK_SIZE, Hkv, D))
    cases.append((
        "paged decode B=8 bs=16", (dec[0],) + pools,
        lambda q, kp, vp: ops.paged_decode_attention(
            q, kp, vp, block_tables=tables, lengths=lengths, impl="pallas"),
        lambda q, kp, vp: ops.paged_decode_attention(
            q, kp, vp, block_tables=tables, lengths=lengths, impl="dense")))
    # MemCom 1-head cross-attention: m memory queries over t source reps
    xq = (rand(1, m, d_model), rand(1, PROMPT_TOKENS, d_model),
          rand(1, PROMPT_TOKENS, d_model))
    cases.append((
        f"memcom_xattn D={d_model} m={m} t={PROMPT_TOKENS}", xq,
        lambda q, k, v: ops.memcom_xattn(q, k, v, impl="pallas"),
        ref.memcom_xattn_ref))

    for name, args, kernel, oracle_fn in cases:
        want = oracle(oracle_fn, *args)
        for dtype in ("float32", "bfloat16"):
            got = kernel(*[jnp.asarray(a, dtype) for a in args])
            got = np.asarray(jax.block_until_ready(got), np.float32)
            err = float(np.abs(got - want).max())
            say(f"kernel {name} {dtype}: max|err| {err:.3e} "
                f"(tol {KERNEL_TOL[dtype]:.0e})")
            check(np.isfinite(got).all(), f"{name} {dtype}: non-finite")
            check(err <= KERNEL_TOL[dtype],
                  f"{name} {dtype}: max|err| {err} > {KERNEL_TOL[dtype]}")


def _many_shot_prompt(np, seed: int):
    from repro.data import (ICLTaskSpec, SyntheticVocab,
                            build_manyshot_prompt, make_episode)

    rng = np.random.default_rng(seed)
    vocab = SyntheticVocab()
    task = ICLTaskSpec(vocab, num_labels=8, keys_per_label=4)
    prompt = build_manyshot_prompt(task, make_episode(task, rng), rng,
                                   budget=PROMPT_TOKENS)
    check(len(prompt) == PROMPT_TOKENS,
          f"shot set has {len(prompt)} tokens, want {PROMPT_TOKENS}")
    return vocab, prompt


def _requests(np, vocab, seed: int, tasks):
    """``REQUESTS`` ragged greedy requests (prompts of 4-16 tokens,
    ``max_new`` 4-12); request i names ``tasks[i % len(tasks)]``, a
    ``(prefix name, raw shots or None)`` pair."""
    from repro.serving import Request

    rng = np.random.default_rng(seed + 1)
    lens = rng.integers(4, 17, REQUESTS)
    news = rng.integers(4, 13, REQUESTS)
    out = []
    for i, (n, k) in enumerate(zip(lens, news)):
        name, shots = tasks[i % len(tasks)]
        out.append(Request(
            tokens=rng.integers(4, vocab.size, int(n)).astype(np.int32),
            max_new=int(k), prefix=name, raw_shots=shots, stop_token=None))
    return out


def _decode_probe(jax, jnp, np, engine, label: str):
    """Compile the engine's own decode-step program ahead of time, check
    it holds Pallas kernels, and run it once on the engine's cache with
    every slot at its seated length: the logits must be finite."""
    t0 = time.perf_counter()
    lengths = jnp.asarray(engine.base, jnp.int32)
    tok = jnp.zeros((engine.slots, 1), jnp.int32)
    args = (engine.params, engine.cache, tok, lengths)
    if engine.kv_layout == "paged":
        args += (jnp.asarray(engine.tables),)
    compiled = engine._decode.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{label}: decode step holds no Pallas kernel")
    # a paged engine's step takes its cache donated: keep the one returned
    logits, engine.cache = compiled(*args)
    logits = np.asarray(jax.block_until_ready(logits), np.float32)
    check(np.isfinite(logits).all(), f"{label}: non-finite decode logits")
    say(f"{label}: decode step compiled in {time.perf_counter() - t0:.2f}s "
        f"with tpu_custom_call; logits {logits.shape} finite, "
        f"|max| {np.abs(logits).max():.3f}")


def _serve(jax, engine, reqs, label: str):
    t0 = time.perf_counter()
    out = engine.serve(reqs)
    dt = time.perf_counter() - t0
    for r in reqs:
        got = len(out.get(r.uid, ()))
        check(got == r.max_new,
              f"{label}: request {r.uid} produced {got}/{r.max_new} tokens")
    n = sum(r.max_new for r in reqs)
    say(f"{label}: {len(reqs)}/{len(reqs)} requests complete, {n} tokens "
        f"in {dt:.2f}s (first serve, step programs compiled inside)")
    return [out[r.uid].tolist() for r in reqs]


def serve_paths(jax, jnp, np, seed: int):
    from repro.configs import get_config
    from repro.core import memcom
    from repro.serving import ServingEngine, materialize_prefix

    cfg = get_config("smollm-360m")
    m = cfg.memcom.num_memory_tokens
    say(f"model {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
        f"d_model {cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads "
        f"of {cfg.hd}, vocab {cfg.vocab_size}, {cfg.dtype}, m={m}")
    vocab, prompt = _many_shot_prompt(np, seed)
    check(vocab.size <= cfg.vocab_size, "synthetic ids exceed the vocab")

    t0 = time.perf_counter()
    target, compressor = jax.jit(lambda: memcom.init_models(cfg, seed=seed))()
    jax.block_until_ready((target, compressor))
    say(f"init (seed {seed}): {time.perf_counter() - t0:.2f}s")

    # ---- offline: compress, materialize, register; dense layout ----
    toks = jnp.asarray(prompt[None])
    t0 = time.perf_counter()
    compress = jax.jit(
        lambda mc, tp, t: materialize_prefix(tp, cfg,
                                             memcom.compress(mc, cfg, t)[0]))
    compiled = compress.lower(compressor, target, toks).compile()
    t_compile = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          "compress program holds no Pallas kernel")
    t0 = time.perf_counter()
    kv = jax.block_until_ready(compiled(compressor, target, toks))
    t_run = time.perf_counter() - t0
    leaves = jax.tree.leaves(kv)
    check(all(bool(jnp.isfinite(x).all()) for x in leaves),
          "materialized prefix is not finite")
    say(f"offline compress {PROMPT_TOKENS} -> {m} slots "
        f"({PROMPT_TOKENS // m}x): compile {t_compile:.2f}s, run "
        f"{t_run:.3f}s; prefix {sum(x.nbytes for x in leaves) / 1e6:.1f} MB")

    max_len = m + 64
    dense = ServingEngine(cfg, target, slots=SLOTS, max_len=max_len)
    dense.add_prefix("task0", kv)
    reqs = _requests(np, vocab, seed, [("task0", None)])
    offline = _serve(jax, dense, reqs, "offline/dense")
    t0 = time.perf_counter()
    dense.serve(_requests(np, vocab, seed, [("task0", None)]))
    say(f"offline/dense: second serve (warm) {time.perf_counter() - t0:.2f}s")
    _decode_probe(jax, jnp, np, dense, "offline/dense")
    del dense, kv

    # ---- online: raw shots compiled in the serving loop; paged layout.
    # Two tasks: the engine compiles task0 whole while nothing decodes,
    # then task1 in COMPILE_BUDGET-token chunks behind task0's decode
    # steps — the interleaved path the budget exists for.
    _, prompt1 = _many_shot_prompt(np, seed + 7)
    paged = ServingEngine(cfg, target, slots=SLOTS, max_len=max_len,
                          kv_layout="paged", block_size=BLOCK_SIZE,
                          compressor=compressor,
                          compile_token_budget=COMPILE_BUDGET)
    reqs = _requests(np, vocab, seed, [("task0", prompt), ("task1", prompt1)])
    online = _serve(jax, paged, reqs, "online/paged")
    cs = paged.stats()["compiler"]
    es = paged.stats()["engine"]
    check(cs["tokens"] == 2 * PROMPT_TOKENS and cs["compiled"] == 2,
          f"online compile consumed {cs['tokens']} tokens, "
          f"{cs['compiled']} job(s)")
    check(es["decode_steps_during_compile"] > 0,
          "no decode step ran while a task compiled")
    say(f"online/paged: {cs['jobs']} compile jobs, {cs['deduped']} deduped "
        f"submits, {cs['chunks']} chunks (budget {COMPILE_BUDGET} tokens "
        f"while decoding), {es['decode_steps_during_compile']} decode steps "
        "during compile")
    _decode_probe(jax, jnp, np, paged, "online/paged")

    # task0's requests saw the same shots offline (one-shot compress) and
    # online (whole-task chunk): report how many greedy tokens agree
    pairs = [(x, y) for x, y in zip(offline[0::2], online[0::2])]
    same = sum(a == b for x, y in pairs for a, b in zip(x, y))
    total = sum(len(x) for x, _ in pairs)
    say(f"offline vs online greedy token agreement (task0): {same}/{total}")


def tensor_parallel(jax, jnp, np, seed: int):
    """gemma2-2b compressed and served on a 1x4 model mesh.  The logits of
    the last prefill row and of the first decode step behind the mesh's
    prefix are compared with the same target on one chip and with a
    float32 one-chip reference; the mesh compressor's prefix is compared
    with the prefix the compressor makes alone on one chip."""
    from repro.configs import get_config
    from repro.core import memcom
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as tfm
    from repro.serving import ServingEngine, materialize_prefix
    from repro.serving.prefix_store import write_prefix_to_cache
    from repro.sharding.rules import BASELINE_RULES
    from repro.sharding.serving import constrain_cache

    cfg = get_config("gemma2-2b")
    m = cfg.memcom.num_memory_tokens
    mesh = make_serving_mesh(model=4)
    say(f"model {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads of {cfg.hd}, "
        f"vocab {cfg.vocab_size}, {cfg.dtype}, m={m}, mesh 1x4")
    vocab, prompt = _many_shot_prompt(np, seed)

    t0 = time.perf_counter()
    target, compressor = memcom.init_models(cfg, mesh, BASELINE_RULES,
                                             seed=seed)
    jax.block_until_ready((target, compressor))
    say(f"sharded init: {time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    kv = jax.jit(lambda mc, tp, t: materialize_prefix(
        tp, cfg, memcom.compress(mc, cfg, t, mesh=mesh)[0]))(
            compressor, target, jnp.asarray(prompt[None]))
    kv = jax.block_until_ready(kv)
    say(f"compress on the mesh: {time.perf_counter() - t0:.2f}s "
        "(compile included)")

    engine = ServingEngine(cfg, target, slots=SLOTS, max_len=m + 64,
                           mesh=mesh, compressor=compressor)
    engine.add_prefix("task0", kv)
    _serve(jax, engine, _requests(np, vocab, seed, [("task0", None)])[:SLOTS],
           "tp/dense")
    _decode_probe(jax, jnp, np, engine, "tp/dense")
    del engine

    query = np.random.default_rng(seed + 2).integers(
        4, vocab.size, 12).astype(np.int32)

    def first_decode(cfg, params, prefix, mesh):
        """Prefill the query behind the prefix, then one decode step fed
        the query's first token (the same input on every side, whatever
        its argmax): the last prefill row's and the step's logits."""
        cache = write_prefix_to_cache(cfg, tfm.init_cache(cfg, 1, m + 64),
                                      prefix)
        cache = constrain_cache(cache, mesh)
        logits, aux = tfm.forward(params, cfg, tokens=jnp.asarray(query[None]),
                                  cache=cache, cache_index=m, mask_offset=m,
                                  mesh=mesh)
        lengths = jnp.full((1,), m + len(query), jnp.int32)
        step, _ = tfm.forward(params, cfg, tokens=jnp.asarray(query[None, :1]),
                              cache=aux["cache"], cache_index=lengths,
                              decode=True, mesh=mesh)
        return (logits[0, -1].astype(jnp.float32),
                step[0, -1].astype(jnp.float32))

    def run(cfg, params, prefix, mesh):
        out = jax.jit(lambda p, k: first_decode(cfg, p, k, mesh))(params,
                                                                   prefix)
        out = [np.asarray(x) for x in out]
        check(all(np.isfinite(x).all() for x in out), "non-finite logits")
        return out

    tp = run(cfg, target, kv, mesh)
    # the one-chip side: both models come off the mesh through the host,
    # and the compressor, alone on chip 0, compresses the same shots
    dev = jax.devices()[0]
    host_t, host_c = jax.device_get((target, compressor))
    for x in jax.tree.leaves((target, compressor)):
        x.delete()
    del target, compressor
    compressor1 = jax.device_put(host_c, dev)
    del host_c
    kv_c = jax.block_until_ready(jax.jit(lambda mc, t: memcom.compress(
        mc, cfg, t)[0])(compressor1, jnp.asarray(prompt[None])))
    for x in jax.tree.leaves(compressor1):
        x.delete()
    del compressor1
    target1, kv1 = jax.device_put((host_t, kv), dev)
    del host_t
    kv_c = jax.jit(lambda tp, p: materialize_prefix(tp, cfg, p))(target1,
                                                                  kv_c)
    sq = lambda t: sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                       for x in jax.tree.leaves(t))
    rel_kv = (sq(jax.tree.map(jnp.subtract, kv_c, kv1)) / sq(kv1)) ** 0.5
    say(f"prefix of the mesh's compressor vs one chip's: relative L2 "
        f"difference {rel_kv:.4e}")
    single = run(cfg, target1, kv1, None)
    single_c = run(cfg, target1, kv_c, None)
    # float32 reference on one chip: cast leaf by leaf, freeing each bf16
    # leaf as it goes, so the 10.5 GB f32 copy fits next to nothing else
    leaves, treedef = jax.tree.flatten(target1)
    del target1
    for i, x in enumerate(leaves):
        leaves[i] = x.astype(jnp.float32)
        x.delete()
    cfg32 = cfg.replace(dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = run(cfg32, jax.tree.unflatten(treedef, leaves),
                  jax.tree.map(lambda x: x.astype(jnp.float32), kv1), None)
    for i, name in enumerate(("prefill row", "decode step")):
        err_tp = float(np.abs(tp[i] - ref[i]).max())
        err_1 = float(np.abs(single[i] - ref[i]).max())
        d_c = float(np.abs(single_c[i] - single[i]).max())
        top = [int(x.argmax()) for x in (tp[i], single[i], ref[i],
                                         single_c[i])]
        say(f"{name} logits vs f32 one-chip reference (max|logit| "
            f"{np.abs(ref[i]).max():.4f}): 1x4 mesh bf16 max|err| "
            f"{err_tp:.4e}, one chip bf16 {err_1:.4e}, mesh vs one chip "
            f"{np.abs(tp[i] - single[i]).max():.4e}; one chip fed the "
            f"one-chip compressor's prefix vs the mesh's {d_c:.4e}; argmax "
            f"mesh/one/ref/one-chip prefix {'/'.join(map(str, top))}")
        check(err_tp <= TP_BF16_ERR_FACTOR * err_1,
              f"{name}: the mesh's bf16 error {err_tp} exceeds "
              f"{TP_BF16_ERR_FACTOR} x the one-chip bf16 error {err_1}")
        check(d_c <= COMPRESS_ERR_FACTOR * err_1,
              f"{name}: the mesh compressor's prefix moves the logits by "
              f"{d_c}, over {COMPRESS_ERR_FACTOR} x the one-chip bf16 "
              f"error {err_1}")
        check(len(set(top)) == 1, f"{name}: argmaxes differ {top}")


def launcher():
    """The launcher's own entry point at smollm-360m's published widths:
    offline compress of two 3,072-token tasks, dense serving of
    ``REQUESTS`` requests of 8 new tokens over ``SLOTS`` slots."""
    from repro.launch import serve

    max_new = 8
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "metrics.json")
        serve.main(["--arch", "smollm-360m", "--tasks", "2",
                    "--context-tokens", str(PROMPT_TOKENS),
                    "--requests", str(REQUESTS), "--slots", str(SLOTS),
                    "--max-new", str(max_new), "--metrics", path])
        with open(path) as f:
            metrics = json.load(f)
    check(metrics["arch"] == "smollm-360m" and metrics["mesh"] is None,
          f"launcher ran {metrics['arch']} mesh={metrics['mesh']}")
    check(metrics["generated"] == REQUESTS * max_new,
          f"launcher generated {metrics['generated']} tokens, want "
          f"{REQUESTS * max_new}")
    say(f"launcher: {metrics['generated']} tokens for {REQUESTS} requests; "
        f"compress {metrics['compress_s']:.2f}s, serve "
        f"{metrics['serve_s']:.2f}s (compiles included)")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel parity + offline/online serving on one "
                         "chip; 4: only the 1x4 tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and inputs")
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "tpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    sys.path.insert(0, str(HERE / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    device = device_check(jax, args.chips)
    say(f"compile cache: {enable_compile_cache()}")
    t_start = time.perf_counter()
    if args.chips == 4:
        tensor_parallel(jax, jnp, np, args.seed)
    else:
        t0 = time.perf_counter()
        kernel_parity(jax, jnp, np, args.seed)
        say(f"kernel parity: {time.perf_counter() - t0:.2f}s")
        serve_paths(jax, jnp, np, args.seed)
        launcher()
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak device memory: "
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB; total "
        f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
