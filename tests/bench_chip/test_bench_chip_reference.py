"""The chip benchmark's yardstick for the dense family at reduced sizes on
the CPU: the seeded weights, the plain float32 reference against the
repo's ``Model``, and the operation and byte counts against shapes worked
out by hand; the draw and the counts pinned to what they were before the
family had a module of its own."""
import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip import correct as C  # noqa: E402
from benchmarks.chip import weights as W  # noqa: E402
from benchmarks.chip import work  # noqa: E402
from benchmarks.chip.families import dense  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.sharding.policy import ShardingPolicy  # noqa: E402

SEED = 2 ** 31 + 977          # past 32 signed bits, as the driver's are

# reduced shapes of the two published decoders the benchmark serves
SMALL = {
    "granite-3-2b": dict(hidden_size=64, intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, vocab_size=512,
                         rope_theta=10000.0, rms_norm_eps=1e-5,
                         tie_word_embeddings=True, attention_bias=False),
    "qwen2-7b": dict(hidden_size=64, intermediate_size=96,
                     num_hidden_layers=3, num_attention_heads=4,
                     num_key_value_heads=1, vocab_size=384,
                     rope_theta=1e6, rms_norm_eps=1e-5,
                     tie_word_embeddings=False, attention_bias=True),
}


def _small(repo_arch):
    keys = SMALL[repo_arch]
    m = dense.DenseDims.from_keys(f"{repo_arch}-small", keys)
    arch = dataclasses.replace(
        ARCHS[repo_arch], name=m.name, num_layers=m.layers,
        d_model=m.d_model, num_heads=m.heads, num_kv_heads=m.kv_heads,
        head_dim=m.head_dim, d_ff=m.d_ff, vocab_size=m.vocab,
        qkv_bias=m.qkv_bias, tie_embeddings=m.tied,
        rope_theta=m.rope_theta, norm_eps=m.norm_eps)
    return m, arch


@pytest.mark.parametrize("repo_arch", sorted(SMALL))
def test_served_tree_is_the_models(repo_arch):
    m, arch = _small(repo_arch)
    model = Model(arch, ShardingPolicy(mesh=None), param_dtype=jnp.bfloat16)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(dense.served_params, m, W.seed_words(SEED))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert m.param_count() == arch.param_count()[0]


@pytest.mark.parametrize("repo_arch", sorted(SMALL))
def test_one_layer_equals_the_stacked_draw(repo_arch):
    m, _ = _small(repo_arch)
    words = W.seed_words(SEED)
    full = dense.served_params(m, words)
    for layer in range(m.layers):
        one = dense.layer_weights(m, words, np.uint32(layer))
        for name, leaf in one.items():
            np.testing.assert_array_equal(
                np.asarray(leaf, np.float32),
                np.asarray(full["blocks"][name][layer], np.float32))


def test_seeds_give_other_weights_and_the_same_seed_the_same():
    m, _ = _small("granite-3-2b")
    a = dense.table_weights(m, W.seed_words(SEED))["embed"]
    b = dense.table_weights(m, W.seed_words(SEED))["embed"]
    c = dense.table_weights(m, W.seed_words(SEED + 2 ** 32))["embed"]
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(c, np.float32))


@pytest.mark.parametrize("repo_arch", sorted(SMALL))
def test_reference_matches_model_forward(repo_arch):
    """float32 model and reference on the same (bf16-drawn) weights."""
    m, arch = _small(repo_arch)
    words = W.seed_words(SEED)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          dense.served_params(m, words))
    model = Model(arch, ShardingPolicy(mesh=None), param_dtype=jnp.float32)
    tokens = np.random.default_rng(3).integers(0, m.vocab, (3, 11),
                                               dtype=np.int32)
    want = np.asarray(jax.jit(model.forward)(params, jnp.asarray(tokens)))
    got = np.asarray(dense.logits(m, words, tokens, 0))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_reference_reads_the_served_positions():
    m, _ = _small("qwen2-7b")
    words = W.seed_words(SEED)
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, m.vocab, (2, 6), dtype=np.int32)
    served = rng.integers(0, m.vocab, (2, 3), dtype=np.int32)
    ref = C.reference_logits(m, words, prompts, served)
    full = dense.logits(m, words, np.concatenate([prompts, served], 1), 0)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(full)[:, 5:8],
                               rtol=1e-5, atol=1e-5)
    # a token the reference ranks first has no gap; any other has one
    best = np.asarray(jnp.argmax(ref, -1))
    assert C.served_gaps(ref, best).max() == 0.0
    assert C.served_gaps(ref, (best + 1) % m.vocab).min() > 0.0


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_lower_precision_moves_the_logits(quant):
    m, _ = _small("granite-3-2b")
    words = W.seed_words(SEED)
    tokens = np.random.default_rng(1).integers(0, m.vocab, (2, 9))
    ref = np.asarray(dense.logits(m, words, tokens, 4))
    low = np.asarray(dense.logits(m, words, tokens, 4, quant=quant))
    err = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-4 < err < 0.2


def test_counts_match_shapes_worked_by_hand():
    m = dense.DenseDims("hand", vocab=10, d_model=4, layers=1, heads=2,
                    kv_heads=1, head_dim=2, d_ff=8, qkv_bias=False,
                    tied=True, rope_theta=1e4, norm_eps=1e-5)
    # per token: wq 4*2*2 + wk,wv 2*4*1*2 + wo 2*2*4 + mlp 3*4*8 = 144 MACs
    # prefill of 1 x 3: 2*3*144 + causal attention 2*2*(2*2)*6 pairs
    # + the head at the last position 2*4*10
    assert work.prefill_flops(m, 1, 3) == 864 + 96 + 80
    # decode against 5 cached: 2*144 + 2*2*(2*2)*6 + 2*4*10
    assert work.decode_flops(m, 1, 5) == 288 + 96 + 80
    # bytes: layer 152 params + final norm 4 + tied table 40, and k, v of
    # 6 positions (5 read, 1 written) of 1 head of 2, at 2 bytes each
    assert m.layer_params() == 152
    assert work.decode_bytes(m, 1, 5) == 2 * (152 + 4 + 40 + 2 * 2 * 6)
    untied = dataclasses.replace(m, tied=False)
    # an untied head adds its table; the embedding is read at the batch's
    # rows only
    assert work.decode_bytes(untied, 3, 5) == 2 * (
        152 + 4 + 40 + 3 * 4 + 3 * 2 * 2 * 6)


# sha256 (first 16 hex digits) of each served leaf's bf16 bytes at SMALL
# and SEED, as the draw gave them before the dense family had a module of
# its own
PARENT_LEAVES = {
    "granite-3-2b": {
        "['blocks']['attn_norm']": "c436aa057b51dbc4",
        "['blocks']['mlp_norm']": "215eb26145a3490e",
        "['blocks']['wd']": "1125d9f0fa6c26b6",
        "['blocks']['wg']": "a999ff1693842e12",
        "['blocks']['wk']": "31ee41a02cb41e23",
        "['blocks']['wo']": "7a4aa03036783781",
        "['blocks']['wq']": "0fb7f18009d2a1a1",
        "['blocks']['wu']": "289a3ba507f51941",
        "['blocks']['wv']": "f666812ffea4c444",
        "['embed']": "0541d556d2c9057a",
        "['final_norm']": "c335778d95919f31",
    },
    "qwen2-7b": {
        "['blocks']['attn_norm']": "304a9a9bc1a8f1a1",
        "['blocks']['bk']": "ad807ea4e63e1b8a",
        "['blocks']['bq']": "be97c1183eb0817a",
        "['blocks']['bv']": "445ae23ab4499dff",
        "['blocks']['mlp_norm']": "5dea6268b9902c31",
        "['blocks']['wd']": "fc65d07a42044924",
        "['blocks']['wg']": "fab6b73cee84d2bd",
        "['blocks']['wk']": "0de03af1e3dbac35",
        "['blocks']['wo']": "9a06283d00092886",
        "['blocks']['wq']": "fd22b5705e86d4bb",
        "['blocks']['wu']": "a7327f834f98a9e6",
        "['blocks']['wv']": "5685992d0fb2924a",
        "['embed']": "6598f431b56393fd",
        "['final_norm']": "c335778d95919f31",
        "['lm_head']": "eccd717bb51cc7d8",
    },
}


@pytest.mark.parametrize("repo_arch", sorted(SMALL))
def test_the_draw_is_the_one_before_families(repo_arch):
    """The same seed gives a bit-identical served tree."""
    m, _ = _small(repo_arch)
    flat = jax.tree_util.tree_flatten_with_path(
        dense.served_params(m, W.seed_words(SEED)))[0]
    got = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(v).tobytes()).hexdigest()[:16] for k, v in flat}
    assert got == PARENT_LEAVES[repo_arch]


def test_the_counts_at_ta_granites_shapes_are_the_ones_before_families():
    """Prefill of 4 x 128 tokens and the decode steps from 128 to 135
    cached positions, at the published shapes of ``ta-granite``: the
    operations and bytes that ``prefill_mfu``, ``decode_hbm_roofline`` and
    ``step_mfu`` divide by."""
    cfg = json.loads((Path(__file__).resolve().parents[2] / "benchmarks"
                      / "chip" / "configs" / "ta-granite.json").read_text())
    m = dense.DenseDims.from_keys(cfg["served_as"], cfg)
    assert work.prefill_flops(m, 4, 128) == 2502707691520.0
    flops = [20436008960.0, 20437319680.0, 20438630400.0, 20439941120.0,
             20441251840.0, 20442562560.0, 20443873280.0, 20445184000.0]
    nbytes = [5109334016.0, 5109661696.0, 5109989376.0, 5110317056.0,
              5110644736.0, 5110972416.0, 5111300096.0, 5111627776.0]
    for i, cache_len in enumerate(range(128, 136)):
        assert work.decode_flops(m, 4, cache_len) == flops[i]
        assert work.decode_bytes(m, 4, cache_len) == nbytes[i]
