"""The float32 reference against the program's own compress and forward
pass, at a small size on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as W
from chipbench.adapters import dense_gqa_memcom as adapter
from chipbench.reference import dense_gqa_memcom as ref

TINY = {
    "name": "tiny", "architecture": "dense_gqa_memcom",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "max_position_embeddings": 512,
    "torch_dtype": "float32", "num_memory_tokens": 8,
}


def program_logits(conf, seed, shots, query):
    from repro.core import memcom
    from repro.models import transformer as tfm
    from repro.serving import materialize_prefix

    cfg = adapter.program_config(conf)
    target, comp = adapter.program_weights(cfg, seed)
    with jax.default_matmul_precision("highest"):
        prefix, _ = memcom.compress(comp, cfg, jnp.asarray(shots[None]))
        kv = materialize_prefix(target, cfg, prefix)
        m = conf["num_memory_tokens"]
        logits, _ = tfm.forward(target, cfg, tokens=jnp.asarray(query[None]),
                                prefix=jax.tree.map(lambda x: x, kv),
                                mask_offset=m)
    return np.asarray(logits[0], np.float32)


# Mistral's own shape at a tiny width: untied head, RoPE theta 1e6, heads
# of 128 lanes, four query heads to each key/value head
MISTRAL_SHAPE = dict(TINY, hidden_size=512, intermediate_size=192,
                     num_attention_heads=4, num_key_value_heads=1,
                     head_dim=128, rope_theta=1e6, tie_word_embeddings=False)


@pytest.mark.parametrize("conf", [dict(TINY, tie_word_embeddings=True),
                                  dict(TINY, tie_word_embeddings=False),
                                  MISTRAL_SHAPE],
                         ids=["True", "False", "mistral_shape"])
def test_reference_matches_program(conf):
    rng = np.random.default_rng(0)
    shots = rng.integers(0, conf["vocab_size"], 40).astype(np.int32)
    query = rng.integers(0, conf["vocab_size"], 6).astype(np.int32)
    want = program_logits(conf, 123, shots, query)
    got = ref.logits(conf, 123, [shots], [(0, query, np.arange(6))])
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_reference_blocks_several_tasks_and_padding():
    """Tasks of different lengths and requests of different lengths in
    one call give the rows each gives alone."""
    conf = TINY
    rng = np.random.default_rng(1)
    shots = [rng.integers(0, 128, n).astype(np.int32) for n in (40, 300)]
    qs = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 11)]
    both = ref.logits(conf, 5, shots, [(1, qs[0], [2, 4]), (0, qs[1], [10])])
    a = ref.logits(conf, 5, [shots[1]], [(0, qs[0], [2, 4])])
    b = ref.logits(conf, 5, [shots[0]], [(0, qs[1], [10])])
    np.testing.assert_allclose(both, np.concatenate([a, b]), atol=1e-5)


def test_control_departs_from_reference():
    conf = TINY
    rng = np.random.default_rng(2)
    shots = [rng.integers(0, 128, 64).astype(np.int32)]
    q = [(0, rng.integers(0, 128, 8).astype(np.int32), np.arange(8))]
    exact = ref.logits(conf, 9, shots, q)
    low = ref.logits(conf, 9, shots, q, control=True)
    err = np.abs(low - exact).max()
    assert 1e-3 < err < 1.0


def test_seeded_weights_stack_equals_per_layer():
    key = W.root_key(2**31 + 12345)
    stacked = W.stacked(key, "target/period/l0/attn/wq", 3, (8, 4),
                        jnp.bfloat16)
    for i in range(3):
        one = W.leaf(key, "target/period/l0/attn/wq", i, (8, 4), jnp.bfloat16)
        assert bool(jnp.all(stacked[i] == one))
    assert not bool(jnp.all(stacked[0] == stacked[1]))
