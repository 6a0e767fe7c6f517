"""Plain float32 reference: a dense GQA decoder with MemCom compression.

The architecture is the Llama/Mistral block (RMSNorm, rotary positions on
split halves, grouped-query attention, SwiGLU MLP) used three times, as
MemCom (arXiv:2510.16092) prescribes:

* the Source-LLM reads a task's many-shot prompt causally and hands the
  input of each layer i, H^i, to the Memory-LLM;
* the Memory-LLM reads the m learned memory tokens causally; after the
  self-attention of layer i a one-head cross-attention of width d_model
  (query from a normed memory stream, keys and values H^i) is added, and
  the result O^i is the layer's compressed context;
* the target projects O^i through its own layer-i key/value weights
  (positions 0..m-1) and reads the query at positions m, m+1, ... behind
  those m slots, causally among its own tokens.

Everything runs in float32 under highest matmul precision, with no kernel,
cache or batching across sequences.  Work is done layer by layer (each
layer's weights regenerated from the seed, then dropped) and attention in
blocks of query rows, so a configuration whose float32 weights do not fit
on the chip still fits one layer at a time.

``control=True`` computes every linear layer with both operands rounded
to float8 (e4m3, one power-of-two scale per tensor): the precision below
the configuration's bfloat16, for the control of the comparison.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


@dataclass(frozen=True)
class Dims:
    """The sizes a configuration file states (Hugging Face key names)."""

    d: int
    f: int
    hq: int
    hkv: int
    hd: int
    layers: int
    vocab: int
    theta: float
    eps: float
    tied: bool
    m: int
    dtype: str

    @staticmethod
    def of(c: dict) -> "Dims":
        return Dims(
            d=c["hidden_size"], f=c["intermediate_size"],
            hq=c["num_attention_heads"], hkv=c["num_key_value_heads"],
            hd=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            layers=c["num_hidden_layers"], vocab=c["vocab_size"],
            theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            tied=bool(c["tie_word_embeddings"]), m=c["num_memory_tokens"],
            dtype=c["torch_dtype"])


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / _FP8_MAX)))
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, control):
    if control:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x (S, H, hd), pos (S,): rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, q_pos, kv_pos, kv_valid, causal):
    """GQA attention of one sequence, in blocks of query rows.  q (S, Hq,
    hd), k/v (T, Hkv, hd); query head h reads key/value head h // G."""
    S, hq, hd = q.shape
    hkv = k.shape[1]
    blk = 256 if S % 256 == 0 else S
    qb = q.reshape(S // blk, blk, hkv, hq // hkv, hd)
    pb = q_pos.reshape(S // blk, blk)

    def one(args):
        qq, pp = args
        s = jnp.einsum("bhgd,thd->hgbt", qq, k, precision=HIGHEST) * hd ** -0.5
        mask = kv_valid[None, :]
        if causal:
            mask = mask & (kv_pos[None, :] <= pp[:, None])
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgbt,thd->bhgd", p, v, precision=HIGHEST)

    return jax.lax.map(one, (qb, pb)).reshape(S, hq * hd)


class _Layer:
    """Layer ``i`` of one stack, its weights regenerated from the seed."""

    def __init__(self, dims: Dims, key, stack: str, i):
        self.dims, self.key, self.stack, self.i = dims, key, stack, i

    def w(self, name, shape):
        return W.leaf(self.key, f"{self.stack}/period/l0/{name}", self.i,
                      shape, self.dims.dtype).astype(F32)

    def qkv(self, x, pos, control):
        D = self.dims
        q = _mm(x, self.w("attn/wq", (D.d, D.hq * D.hd)), control)
        k = _mm(x, self.w("attn/wk", (D.d, D.hkv * D.hd)), control)
        v = _mm(x, self.w("attn/wv", (D.d, D.hkv * D.hd)), control)
        q = _rope(q.reshape(-1, D.hq, D.hd), pos, D.theta)
        k = _rope(k.reshape(-1, D.hkv, D.hd), pos, D.theta)
        return q, k, v.reshape(-1, D.hkv, D.hd)

    def out(self, o, control):
        D = self.dims
        return _mm(o, self.w("attn/wo", (D.hq * D.hd, D.d)), control)

    def mlp(self, h, control):
        D = self.dims
        x = _norm(h, self.w("norm2/scale", (D.d,)), D.eps)
        g = _mm(x, self.w("mlp/wg", (D.d, D.f)), control)
        u = _mm(x, self.w("mlp/wi", (D.d, D.f)), control)
        return h + _mm(jax.nn.silu(g) * u, self.w("mlp/wo", (D.f, D.d)),
                       control)

    def self_attn(self, h, pos, control):
        """Causal self-attention block (residual added)."""
        D = self.dims
        x = _norm(h, self.w("norm1/scale", (D.d,)), D.eps)
        q, k, v = self.qkv(x, pos, control)
        valid = jnp.ones(pos.shape, bool)
        return h + self.out(_attend(q, k, v, pos, pos, valid, True), control)


def _layer_step(dims: Dims, control: bool, key, i, src, src_len, mem, tgt,
                tgt_task):
    D = dims
    Ls, m, St = src.shape[1], mem.shape[1], tgt.shape[1]
    src_pos = jnp.arange(Ls, dtype=jnp.int32)
    mem_pos = jnp.arange(m, dtype=jnp.int32)
    source = _Layer(D, key, "source", i)
    memory = _Layer(D, key, "memory_llm", i)
    target = _Layer(D, key, "target", i)

    def memx(name, shape):
        return W.leaf(key, f"memx/period/l0/memx/{name}", i, shape,
                      D.dtype).astype(F32)

    def src_one(args):
        h, n = args
        x = _norm(h, source.w("norm1/scale", (D.d,)), D.eps)
        q, k, v = source.qkv(x, src_pos, control)
        o = _attend(q, k, v, src_pos, src_pos, src_pos < n, True)
        return source.mlp(h + source.out(o, control), control)

    def mem_one(args):
        h, H, n = args  # H: this layer's Source-LLM input H^i
        h = memory.self_attn(h, mem_pos, control)
        qn = _norm(h, memx("norm/scale", (D.d,)), D.eps)
        q = _mm(qn, memx("wq", (D.d, D.d)), control)
        k = _mm(H, memx("wk", (D.d, D.d)), control)
        v = _mm(H, memx("wv", (D.d, D.d)), control)
        s = jnp.matmul(q, k.T, precision=HIGHEST) * D.d ** -0.5
        s = jnp.where((src_pos < n)[None, :], s, -jnp.inf)
        o = jnp.matmul(jax.nn.softmax(s, -1), v, precision=HIGHEST)
        h = h + _mm(o, memx("wo", (D.d, D.d)), control)
        return memory.mlp(h, control), h  # (next layer's input, O^i)

    new_src = jax.lax.map(src_one, (src, src_len))
    new_mem, omega = jax.lax.map(mem_one, (mem, src, src_len))

    def prefix_kv(o):
        _, k, v = target.qkv(o, mem_pos, control)
        return k, v

    k_pre, v_pre = jax.lax.map(prefix_kv, omega)
    tq_pos = m + jnp.arange(St, dtype=jnp.int32)
    kv_pos = jnp.concatenate([mem_pos, tq_pos])
    kv_valid = jnp.ones(kv_pos.shape, bool)

    def tgt_one(args):
        h, t = args
        x = _norm(h, target.w("norm1/scale", (D.d,)), D.eps)
        q, k, v = target.qkv(x, tq_pos, control)
        k = jnp.concatenate([k_pre[t], k], 0)
        v = jnp.concatenate([v_pre[t], v], 0)
        o = _attend(q, k, v, tq_pos, kv_pos, kv_valid, True)
        return target.mlp(h + target.out(o, control), control)

    new_tgt = jax.lax.map(tgt_one, (tgt, tgt_task))
    return new_src, new_mem, new_tgt


@partial(jax.jit, static_argnums=(0, 1))
def _embed(dims: Dims, control: bool, key, src_tok, tgt_tok):
    D = dims
    e_src = W.leaf(key, "source/embed/tokens", None, (D.vocab, D.d), D.dtype)
    e_tgt = W.leaf(key, "target/embed/tokens", None, (D.vocab, D.d), D.dtype)
    mem = W.leaf(key, "mem_tokens", None, (D.m, D.d), D.dtype).astype(F32)
    K = src_tok.shape[0]
    return (e_src[src_tok].astype(F32),
            jnp.broadcast_to(mem[None], (K, D.m, D.d)),
            e_tgt[tgt_tok].astype(F32))


@partial(jax.jit, static_argnums=(0, 1))
def _logits(dims: Dims, control: bool, key, rows):
    D = dims
    x = _norm(rows, W.leaf(key, "target/final_norm/scale", None, (D.d,),
                           D.dtype).astype(F32), D.eps)
    if D.tied:
        e = W.leaf(key, "target/embed/tokens", None, (D.vocab, D.d), D.dtype)
        return _mm(x, e.astype(F32).T, control)
    head = W.leaf(key, "target/lm_head", None, (D.d, D.vocab), D.dtype)
    return _mm(x, head.astype(F32), control)


_layer_jit = jax.jit(_layer_step, static_argnums=(0, 1))


def logits(config: dict, seed: int, shots, queries, *, control=False):
    """Reference logits of served tokens.

    ``shots``: the distinct tasks' many-shot prompts (int arrays).
    ``queries``: ``(task index, fed tokens, read positions)`` per request,
    where the fed tokens are the prompt followed by all served tokens but
    the last, and the read positions are those whose next-token logits
    predicted a served token.  Returns a (positions, vocab) float32 numpy
    array, rows in the order of ``queries`` and their read positions.
    """
    D = Dims.of(config)
    key = W.root_key(seed)
    # shapes rounded up to buckets, so that samples of different sizes
    # share compiled programs; padded tasks and rows are never read
    K = _up(len(shots), 8)
    Ls = _up(max(len(s) for s in shots), 256)
    src_tok = np.zeros((K, Ls), np.int32)
    src_len = np.ones((K,), np.int32)
    for j, s in enumerate(shots):
        src_tok[j, :len(s)] = s
        src_len[j] = len(s)
    St = _up(max(len(f) for _, f, _ in queries), 32)
    tgt_tok = np.zeros((_up(len(queries), 16), St), np.int32)
    tgt_task = np.zeros((len(tgt_tok),), np.int32)
    for r, (t, fed, _) in enumerate(queries):
        tgt_tok[r, :len(fed)] = fed
        tgt_task[r] = t
    with jax.default_matmul_precision("highest"):
        src, mem, tgt = _embed(D, control, key, src_tok, tgt_tok)
        for i in range(D.layers):
            src, mem, tgt = _layer_jit(D, control, key, np.int32(i), src,
                                       src_len, mem, tgt, tgt_task)
        del src, mem
        rr = np.concatenate([np.full(len(p), r) for r, (_, _, p)
                             in enumerate(queries)])
        pp = np.concatenate([np.asarray(p) for _, _, p in queries])
        n = len(rr)
        rr = np.pad(rr, (0, _up(n, 64) - n))
        pp = np.pad(pp, (0, _up(n, 64) - n))
        out = _logits(D, control, key, tgt[rr, pp])
    return np.asarray(out, np.float32)[:n]


def _up(n: int, k: int) -> int:
    return -(-n // k) * k
