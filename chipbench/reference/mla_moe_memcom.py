"""Plain float32 reference: a DeepSeek-V2 decoder with MemCom compression.

The block is DeepSeek-V2's (arXiv:2405.04434, the Hugging Face
``modeling_deepseek.py`` of DeepSeek-V2-Lite): RMSNorm; multi-head latent
attention in its published, non-absorbed form — queries straight from
the hidden state when ``q_lora_rank`` is null, keys and values expanded
per head from the normed latent ``c_kv`` through ``W_ukv``, and one
rotary key head of ``qk_rope_head_dim`` lanes shared by every head; YaRN
rotary positions with the softmax scale ``qk_head_dim ** -0.5 *
mscale(factor, mscale_all_dim) ** 2``; then a SwiGLU MLP in the first
``first_k_dense_replace`` layers and a mixture of experts in the rest:
softmax router over every routed expert in float32, greedy top-k, gates
not renormalised when ``norm_topk_prob`` is false (``routed_scaling_factor``
must be 1, as DeepSeek-V2-Lite publishes it, so the gates are not
scaled), plus the shared experts (one SwiGLU of ``n_shared_experts``
times the expert width).

MemCom (arXiv:2510.16092) uses the architecture three times, as in
``dense_gqa_memcom``: the Source-LLM reads the shots and hands each
layer's input H^i on; the Memory-LLM reads the m memory tokens, and after
the self-attention of layer i adds a one-head cross-attention of width
``hidden_size`` onto H^i, whose result O^i is the layer's compressed
context; the target puts O^i through its own ``W_dkv``, ``kv_norm`` and
rotary positions 0..m-1 as the layer's latent prefix and reads the query
behind those m rows.

Departures from the published model:

* the expert share: the layers hold the experts ``[expert_offset,
  expert_offset + n_routed_experts)`` of the router's
  ``published["n_routed_experts"]``; a token's pairs with experts held
  elsewhere add nothing (that chip's part of the result), as in the
  program;
* seeded random weights (:mod:`chipbench.weights`; every norm gain 1);
* rotary positions rotate the two halves of the rope lanes, where the
  published code first interleaves them: a fixed permutation of the rope
  lanes of queries and keys alike, which seeded weights absorb.

Everything runs in float32 under highest matmul precision, with every
routed pair of every token computed (no capacity, nothing dropped), no
kernel, cache or batching across sequences.  Work is done layer by layer
(each layer's weights regenerated from the seed, then dropped) and
attention in blocks of query rows, so the reference fits on the chip.

``control=True`` computes every linear layer (the router included) with
both operands rounded to float8 (e4m3, one power-of-two scale per
tensor): the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import math
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
    heads: int
    rank: int          # kv_lora_rank
    nope: int          # qk_nope_head_dim
    rope: int          # qk_rope_head_dim
    vd: int            # v_head_dim
    f: int             # intermediate_size (dense layers)
    ef: int            # moe_intermediate_size
    experts: int       # the router's width
    held: int          # experts held here
    offset: int
    top_k: int
    shared: int
    norm_topk: bool
    dense: int         # first_k_dense_replace
    layers: int
    vocab: int
    theta: float
    eps: float
    yarn: tuple        # factor, original positions, beta_fast, beta_slow,
                       # mscale, mscale_all_dim
    m: int
    dtype: str

    @staticmethod
    def of(c: dict) -> "Dims":
        assert c["q_lora_rank"] is None, "queries without LoRA only"
        assert c["routed_scaling_factor"] == 1, "unscaled gates only"
        rs = c["rope_scaling"]
        return Dims(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], vd=c["v_head_dim"],
            f=c["intermediate_size"], ef=c["moe_intermediate_size"],
            experts=c["published"]["n_routed_experts"],
            held=c["n_routed_experts"], offset=c["expert_offset"],
            top_k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
            norm_topk=bool(c["norm_topk_prob"]),
            dense=c["first_k_dense_replace"], layers=c["num_hidden_layers"],
            vocab=c["vocab_size"], theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"])),
            m=c["num_memory_tokens"], dtype=c["torch_dtype"])


# ---- YaRN, from the published formulas ------------------------------------


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(dims: Dims):
    """(inverse frequencies (rope/2,), cos/sin magnitude, softmax scale)."""
    factor, orig, fast, slow, msc, msc_all = dims.yarn
    dim, base = dims.rope, dims.theta

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    mag = _yarn_mscale(factor, msc) / _yarn_mscale(factor, msc_all)
    scale = (dims.nope + dims.rope) ** -0.5 * _yarn_mscale(factor, msc_all) ** 2
    return inv.astype(np.float32), float(mag), float(scale)


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


def _rope(x, pos, dims: Dims):
    """x (S, ..., rope), pos (S,): YaRN rotation of the two halves."""
    inv, mag, _ = yarn(dims)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    cos = (jnp.cos(ang) * mag).reshape(shape)
    sin = (jnp.sin(ang) * mag).reshape(shape)
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, q_pos, kv_pos, kv_valid, scale):
    """Causal multi-head attention of one sequence, in blocks of query
    rows.  q/k (S|T, H, qk), v (T, H, vd)."""
    S, H, _ = q.shape
    blk = 256 if S % 256 == 0 else S
    qb = q.reshape(S // blk, blk, H, q.shape[-1])
    pb = q_pos.reshape(S // blk, blk)

    def one(args):
        qq, pp = args
        s = jnp.einsum("bhd,thd->hbt", qq, k, precision=HIGHEST) * scale
        mask = kv_valid[None, :] & (kv_pos[None, :] <= pp[:, None])
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hbt,thd->bhd", p, v, precision=HIGHEST)

    return jax.lax.map(one, (qb, pb)).reshape(S, H * v.shape[-1])


class _Layer:
    """One layer of one stack, its weights regenerated from the seed: the
    leading dense layer ``prefix_<i>`` (``prefix`` = i), else the period's
    ``l0`` at index ``layer``."""

    def __init__(self, dims: Dims, key, stack: str, prefix, layer):
        self.dims, self.key, self.moe = dims, key, prefix is None
        if self.moe:
            self.base, self.layer = f"{stack}/period/l0", layer
        else:
            self.base, self.layer = f"{stack}/prefix_{prefix}", None
        # every weight of the layer, made once here, outside the loops
        # over tasks and rows that read it
        D = dims
        shapes = {
            "norm1/scale": (D.d,), "norm2/scale": (D.d,),
            "attn/wq": (D.d, D.heads * (D.nope + D.rope)),
            "attn/wdkv": (D.d, D.rank + D.rope), "attn/kv_norm": (D.rank,),
            "attn/wukv": (D.rank, D.heads * (D.nope + D.vd)),
            "attn/wo": (D.heads * D.vd, D.d)}
        if self.moe:
            sf = D.shared * D.ef
            shapes.update({
                "moe/router": (D.d, D.experts),
                "moe/wg": (D.held, D.d, D.ef), "moe/wi": (D.held, D.d, D.ef),
                "moe/wo": (D.held, D.ef, D.d),
                "moe/shared/mlp/wg": (D.d, sf), "moe/shared/mlp/wi": (D.d, sf),
                "moe/shared/mlp/wo": (sf, D.d)})
        else:
            shapes.update({"mlp/wg": (D.d, D.f), "mlp/wi": (D.d, D.f),
                           "mlp/wo": (D.f, D.d)})
        self.ws = {name: self._make(name, shape)
                   for name, shape in shapes.items()}

    def _make(self, name, shape):
        if len(shape) == 1:  # norm gains
            return jnp.ones(shape, F32)
        return W.leaf(self.key, f"{self.base}/{name}", self.layer, shape,
                      self.dims.dtype).astype(F32)

    def w(self, name, shape):
        assert self.ws[name].shape == shape, (name, shape)
        return self.ws[name]

    def latent(self, x, pos, control):
        """x (S, d) -> (c_kv normed (S, rank), k_rope (S, rope))."""
        D = self.dims
        kv = _mm(x, self.w("attn/wdkv", (D.d, D.rank + D.rope)), control)
        c = _norm(kv[:, :D.rank], self.w("attn/kv_norm", (D.rank,)), D.eps)
        return c, _rope(kv[:, D.rank:], pos, D)

    def expand(self, c, kr, control):
        """Per-head keys and values from latent rows."""
        D = self.dims
        kv = _mm(c, self.w("attn/wukv", (D.rank, D.heads * (D.nope + D.vd))),
                 control).reshape(-1, D.heads, D.nope + D.vd)
        k = jnp.concatenate(
            [kv[..., :D.nope],
             jnp.broadcast_to(kr[:, None, :], (kr.shape[0], D.heads, D.rope))],
            -1)
        return k, kv[..., D.nope:]

    def queries(self, x, pos, control):
        D = self.dims
        q = _mm(x, self.w("attn/wq", (D.d, D.heads * (D.nope + D.rope))),
                control).reshape(-1, D.heads, D.nope + D.rope)
        return jnp.concatenate([q[..., :D.nope], _rope(q[..., D.nope:], pos, D)],
                               -1)

    def attn(self, x, pos, valid, control, pre=None, pre_pos=None):
        """Self-attention of normed ``x`` at ``pos`` (causal), behind the
        latent prefix rows ``pre`` = (c_kv, k_rope) at ``pre_pos``."""
        D = self.dims
        q = self.queries(x, pos, control)
        c, kr = self.latent(x, pos, control)
        kv_pos, kv_valid = pos, valid
        if pre is not None:
            c = jnp.concatenate([pre[0], c], 0)
            kr = jnp.concatenate([pre[1], kr], 0)
            kv_pos = jnp.concatenate([pre_pos, pos])
            kv_valid = jnp.concatenate([jnp.ones(pre_pos.shape, bool), valid])
        k, v = self.expand(c, kr, control)
        o = _attend(q, k, v, pos, kv_pos, kv_valid, yarn(D)[2])
        return _mm(o, self.w("attn/wo", (D.heads * D.vd, D.d)), control)

    def ffn(self, h, control):
        """The block's channel half (residual added)."""
        D = self.dims
        x = _norm(h, self.w("norm2/scale", (D.d,)), D.eps)
        if not self.moe:
            return h + _swiglu(x, self.w("mlp/wg", (D.d, D.f)),
                               self.w("mlp/wi", (D.d, D.f)),
                               self.w("mlp/wo", (D.f, D.d)), control)
        probs = jax.nn.softmax(
            _mm(x, self.w("moe/router", (D.d, D.experts)), control), -1)
        gates, ids = jax.lax.top_k(probs, D.top_k)
        if D.norm_topk:
            gates = gates / gates.sum(-1, keepdims=True)
        # every token through every held expert at once (the experts side
        # by side), each expert's part weighted by the token's gate for it
        # (0 where the expert is not among its top k)
        wg = self.w("moe/wg", (D.held, D.d, D.ef)).transpose(1, 0, 2)
        wi = self.w("moe/wi", (D.held, D.d, D.ef)).transpose(1, 0, 2)
        wo = self.w("moe/wo", (D.held, D.ef, D.d))
        held = D.offset + jnp.arange(D.held)
        g = jnp.sum(jnp.where(ids[:, :, None] == held, gates[:, :, None], 0.0),
                    1)  # (N, held)
        hid = (jax.nn.silu(_mm(x, wg.reshape(D.d, -1), control))
               * _mm(x, wi.reshape(D.d, -1), control))
        hid = (hid.reshape(-1, D.held, D.ef) * g[:, :, None]).reshape(
            -1, D.held * D.ef)
        y = _mm(hid, wo.reshape(-1, D.d), control)
        sf = D.shared * D.ef
        y = y + _swiglu(x, self.w("moe/shared/mlp/wg", (D.d, sf)),
                        self.w("moe/shared/mlp/wi", (D.d, sf)),
                        self.w("moe/shared/mlp/wo", (sf, D.d)), control)
        return h + y


def _swiglu(x, wg, wi, wo, control):
    return _mm(jax.nn.silu(_mm(x, wg, control)) * _mm(x, wi, control), wo,
               control)


def _layer_step(dims: Dims, control: bool, prefix, key, layer, src,
                src_len, mem, tgt, tgt_task):
    """One layer of all three stacks: the leading dense layer ``prefix``
    (static), or with ``prefix`` None the MoE layer ``layer`` (traced) of
    the period."""
    D = dims
    Ls, m, St = src.shape[1], mem.shape[1], tgt.shape[1]
    src_pos = jnp.arange(Ls, dtype=jnp.int32)
    mem_pos = jnp.arange(m, dtype=jnp.int32)
    source = _Layer(D, key, "source", prefix, layer)
    memory = _Layer(D, key, "memory_llm", prefix, layer)
    target = _Layer(D, key, "target", prefix, layer)
    if prefix is None:
        xname, xlayer = "memx/period/l0/memx", layer
    else:
        xname, xlayer = f"memx/prefix/{prefix}/memx", None

    def memx_w(name, shape):
        if len(shape) == 1:
            return jnp.ones(shape, F32)
        return W.leaf(key, f"{xname}/{name}", xlayer, shape,
                      D.dtype).astype(F32)

    xw = {n: memx_w(n, s) for n, s in (("norm/scale", (D.d,)),
                                       ("wq", (D.d, D.d)), ("wk", (D.d, D.d)),
                                       ("wv", (D.d, D.d)), ("wo", (D.d, D.d)))}
    memx = lambda name, _shape: xw[name]

    def src_one(args):
        h, n = args
        x = _norm(h, source.w("norm1/scale", (D.d,)), D.eps)
        h = h + source.attn(x, src_pos, src_pos < n, control)
        return source.ffn(h, control)

    def mem_one(args):
        h, H, n = args  # H: this layer's Source-LLM input H^i
        x = _norm(h, memory.w("norm1/scale", (D.d,)), D.eps)
        h = h + memory.attn(x, mem_pos, jnp.ones((m,), bool), control)
        qn = _norm(h, memx("norm/scale", (D.d,)), D.eps)
        q = _mm(qn, memx("wq", (D.d, D.d)), control)
        k = _mm(H, memx("wk", (D.d, D.d)), control)
        v = _mm(H, memx("wv", (D.d, D.d)), control)
        s = jnp.matmul(q, k.T, precision=HIGHEST) * D.d ** -0.5
        s = jnp.where((src_pos < n)[None, :], s, -jnp.inf)
        o = jnp.matmul(jax.nn.softmax(s, -1), v, precision=HIGHEST)
        h = h + _mm(o, memx("wo", (D.d, D.d)), control)
        return memory.ffn(h, control), h  # (next layer's input, O^i)

    new_src = jax.lax.map(src_one, (src, src_len))
    new_mem, omega = jax.lax.map(mem_one, (mem, src, src_len))
    # O^i through the target's latent projection: the layer's prefix rows
    c_pre, kr_pre = jax.lax.map(lambda o: target.latent(o, mem_pos, control),
                                omega)
    tq_pos = m + jnp.arange(St, dtype=jnp.int32)

    def tgt_one(args):
        h, t = args
        x = _norm(h, target.w("norm1/scale", (D.d,)), D.eps)
        h = h + target.attn(x, tq_pos, jnp.ones((St,), bool), control,
                            pre=(c_pre[t], kr_pre[t]), pre_pos=mem_pos)
        return target.ffn(h, control)

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
    x = _norm(rows, jnp.ones((D.d,), F32), D.eps)
    head = W.leaf(key, "target/lm_head", None, (D.d, D.vocab), D.dtype)
    return _mm(x, head.astype(F32), control)


_layer_jit = jax.jit(_layer_step, static_argnums=(0, 1, 2))


def logits(config: dict, seed: int, shots, queries, *, control=False):
    """Reference logits of served tokens.

    ``shots``: the distinct tasks' many-shot prompts (int arrays).
    ``queries``: ``(task index, fed tokens, read positions)`` per request,
    where the fed tokens are the prompt followed by all served tokens but
    the last, and the read positions are those whose next-token logits
    predicted a served token.  Returns a (positions, vocab) float32 numpy
    array, rows in the order of ``queries`` and their read positions.
    """
    assert not config["tie_word_embeddings"]
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
            prefix = i if i < D.dense else None
            src, mem, tgt = _layer_jit(D, control, prefix, key,
                                       np.int32(max(i - D.dense, 0)), src,
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
