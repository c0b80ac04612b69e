"""The dense decoder family: Granite-3.0 and Qwen2 as the repo's dense
``Model`` serves them.

**Weights.** The tree is the layout the repo's dense ``Model`` takes
(``embed``, ``final_norm``, ``lm_head`` when untied, and ``blocks`` with a
leading layer axis), drawn from the seed by :mod:`benchmarks.chip.weights`.
Norm weights are offsets, as the program applies them (``x * (1 + w)``).
Biases and norm offsets are drawn non-zero so that the comparison
exercises them.

**Reference.** Straight ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching beyond the rows
it is given. It draws its own weights from the seed, one layer at a time,
so it fits beside nothing: the program's state is freed before it runs.
The equations are those of the published Granite-3.0 and Qwen2 decoders
(pre-norm RMSNorm, rotary positions with the split-half rotation, grouped
query attention, SwiGLU), with the departures the repo's model makes and
the reference copies, so that the two compute the same function:

- norm weights are offsets applied as ``x * (1 + w)``; published
  checkpoints store ``1 + w`` (a re-parametrisation, same function);
- granite-3.0-2b publishes ``embedding_multiplier`` 12,
  ``attention_multiplier`` 0.015625, ``residual_multiplier`` 0.22 and
  ``logits_scaling`` 8; the repo applies none of them: embeddings and
  residuals are unscaled, attention scales by ``head_dim ** -0.5`` and
  logits are not divided;
- qwen2-7b publishes ``rms_norm_eps`` 1e-6; the repo's arch uses 1e-5.

``quant`` computes the same function with every projection and the head
in a lower precision (weights per output channel, activations per token,
symmetric): the control that the comparison in
:mod:`benchmarks.chip.correct` must fail.

**Work.** Operations and bytes a step needs, from its shapes. These count
the work itself, whatever implements it, so that a share of a peak
computed from them cannot pass 100% however a later kernel does the work:

- operations: two per multiply-add of every projection and the gated
  MLP, causal attention over the positions each query may see, and the
  head at the positions whose logits the step returns (the last prompt
  position in prefill, the one new position in decode);
- decode bytes: every weight read once (the embedding only at the rows
  the batch looks up, unless the head is tied to it), the key and value
  cache read up to ``cache_len``, and one position of it written. What the
  repo's code moves beyond that (a one-hot rewrite of the whole cache,
  attention over ``max_seq``) is not work the step needs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import precision as P
from benchmarks.chip import weights as W

NORM_SCALE = 0.1       # norm offsets: scales of 1 +- 0.1
BIAS_SCALE = 0.5       # q/k/v are about unit-scale; a bias of half that
EMBED_SCALE = 0.02
BF16_BYTES = 2


# ---------------------------------------------------------------------------
# dims
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DenseDims:
    """The shape of a dense decoder, read from a configuration file."""
    name: str
    vocab: int
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_keys(cls, name: str, keys: Mapping[str, Any]) -> "DenseDims":
        """From the Hugging Face ``config.json`` keys of a model."""
        heads = int(keys["num_attention_heads"])
        return cls(
            name=name,
            vocab=int(keys["vocab_size"]),
            d_model=int(keys["hidden_size"]),
            layers=int(keys["num_hidden_layers"]),
            heads=heads,
            kv_heads=int(keys["num_key_value_heads"]),
            head_dim=int(keys.get("head_dim")
                         or keys["hidden_size"] // heads),
            d_ff=int(keys["intermediate_size"]),
            qkv_bias=bool(keys["attention_bias"]),
            tied=bool(keys["tie_word_embeddings"]),
            rope_theta=float(keys["rope_theta"]),
            norm_eps=float(keys["rms_norm_eps"]))

    def layer_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], float]]:
        """Per-layer leaf -> (shape without the layer axis, scale)."""
        d, H, KV, hd, f = (self.d_model, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        out = {
            "attn_norm": ((d,), NORM_SCALE),
            "wq": ((d, H, hd), d ** -0.5),
            "wk": ((d, KV, hd), d ** -0.5),
            "wv": ((d, KV, hd), d ** -0.5),
            "wo": ((H, hd, d), (H * hd) ** -0.5),
            "mlp_norm": ((d,), NORM_SCALE),
            "wg": ((d, f), d ** -0.5),
            "wu": ((d, f), d ** -0.5),
            "wd": ((f, d), f ** -0.5),
        }
        if self.qkv_bias:
            out.update(bq=((H, hd), BIAS_SCALE), bk=((KV, hd), BIAS_SCALE),
                       bv=((KV, hd), BIAS_SCALE))
        return out

    def layer_params(self) -> int:
        return sum(int(np.prod(s)) for s, _ in self.layer_shapes().values())

    def param_count(self) -> int:
        head = 0 if self.tied else self.vocab * self.d_model
        return (self.vocab * self.d_model + head + self.d_model
                + self.layers * self.layer_params())


Dims = DenseDims

# Hugging Face config key -> the repo's ArchConfig field
_ARCH_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
}


def repo_arch(m: DenseDims, config: Mapping[str, Any]):
    """The config's ``repo_arch`` with the config's keys, named ``m.name``;
    a ``ValueError`` where the repo would serve another function."""
    from repro.configs import ARCHS
    arch = dataclasses.replace(ARCHS[config["repo_arch"]], name=m.name, **{
        f: config[k] for k, f in _ARCH_FIELDS.items() if k in config})
    if config.get("hidden_act", "silu") != arch.mlp_activation:
        raise ValueError(f"hidden_act {config['hidden_act']} but the repo "
                         f"serves {arch.mlp_activation}")
    if m.head_dim != arch.head_dim:
        raise ValueError("head_dim differs")
    return arch


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
# a fixed index per leaf name: the key of a leaf never depends on which
# other leaves a model has
_LEAF_INDEX = {n: i for i, n in enumerate(
    ("embed", "final_norm", "lm_head", "attn_norm", "wq", "wk", "wv", "wo",
     "bq", "bk", "bv", "mlp_norm", "wg", "wu", "wd"))}


def _leaf_key(words: jax.Array, name: str) -> jax.Array:
    return W.leaf_key(words, _LEAF_INDEX[name])


def _layer(m: DenseDims, words: jax.Array, layer: jax.Array
           ) -> Dict[str, jax.Array]:
    return {n: W.draw(jax.random.fold_in(_leaf_key(words, n), layer), s, sc)
            for n, (s, sc) in m.layer_shapes().items()}


@partial(jax.jit, static_argnums=0)
def layer_weights(m: DenseDims, words: jax.Array, layer: jax.Array
                  ) -> Dict[str, jax.Array]:
    """One layer's leaves, equal to that layer of :func:`served_params`."""
    return _layer(m, words, layer)


@partial(jax.jit, static_argnums=0)
def table_weights(m: DenseDims, words: jax.Array) -> Dict[str, jax.Array]:
    """``embed``, ``final_norm`` and, when untied, ``lm_head``."""
    d, V = m.d_model, m.vocab
    out = {"embed": W.draw(_leaf_key(words, "embed"), (V, d), EMBED_SCALE),
           "final_norm": W.draw(_leaf_key(words, "final_norm"), (d,),
                                NORM_SCALE)}
    if not m.tied:
        out["lm_head"] = W.draw(_leaf_key(words, "lm_head"), (d, V),
                                d ** -0.5)
    return out


@partial(jax.jit, static_argnums=0)
def served_params(m: DenseDims, words: jax.Array) -> Dict[str, Any]:
    """The whole tree in one program, every layer stacked."""
    layers = jnp.arange(m.layers, dtype=jnp.uint32)
    blocks = jax.vmap(lambda l: _layer(m, words, l))(layers)
    return {**table_weights(m, words), "blocks": blocks}


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, T, heads, hd]; rotates the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv         # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "quant"))
def block(h: jax.Array, p: Dict[str, jax.Array], *, m: DenseDims,
          quant: Optional[str]) -> jax.Array:
    """One decoder layer over the whole sequence, causal."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    T = h.shape[1]
    pos = jnp.arange(T)
    x = rms_norm(h, p["attn_norm"], m.norm_eps)
    q = P.mm("btd,dhk->bthk", x, p["wq"], (2,), (0,), quant)
    k = P.mm("btd,dhk->bthk", x, p["wk"], (2,), (0,), quant)
    v = P.mm("btd,dhk->bthk", x, p["wv"], (2,), (0,), quant)
    if m.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
    rep = m.heads // m.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=P.HI) \
        * m.head_dim ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v,
                   precision=P.HI)
    h = h + P.mm("bthk,hkd->btd", o, p["wo"], (2, 3), (0, 1), quant)
    x = rms_norm(h, p["mlp_norm"], m.norm_eps)
    g = P.mm("btd,df->btf", x, p["wg"], (2,), (0,), quant)
    u = P.mm("btd,df->btf", x, p["wu"], (2,), (0,), quant)
    return h + P.mm("btf,fd->btd", jax.nn.silu(g) * u, p["wd"], (2,), (0,),
                    quant)


@jax.jit
def _embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return table[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(h: jax.Array, tables: Dict[str, jax.Array], *, m: DenseDims,
          quant: Optional[str]) -> jax.Array:
    x = rms_norm(h, tables["final_norm"].astype(jnp.float32), m.norm_eps)
    table = (tables["embed"].T if m.tied else tables["lm_head"]).astype(
        jnp.float32)
    return P.mm("btd,dv->btv", x, table, (2,), (0,), quant)


def logits(m: DenseDims, words: np.ndarray, tokens: np.ndarray,
           first: int, quant: Optional[str] = None) -> jax.Array:
    """Float32 logits ``[B, T - first, V]`` at positions ``first..T-1``
    of ``tokens`` ``[B, T]``, the layers run one at a time."""
    P.check_quant(quant)
    tables = table_weights(m, words)
    h = _embed(tables["embed"], jnp.asarray(tokens, jnp.int32))
    for layer in range(m.layers):
        h = block(h, layer_weights(m, words, np.uint32(layer)), m=m,
                  quant=quant)
    return _head(h[:, first:], tables, m=m, quant=quant)


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------
def _proj_macs_per_token(m: DenseDims) -> int:
    """Multiply-adds of the projections and MLP of all layers, per token."""
    d, H, KV, hd, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    return m.layers * per_layer


def prefill_flops(m: DenseDims, batch: int, seq: int) -> float:
    """One prefill of ``batch`` prompts of ``seq`` tokens."""
    proj = 2.0 * batch * seq * _proj_macs_per_token(m)
    pairs = seq * (seq + 1) / 2                        # causal (q, k) pairs
    attn = 2.0 * 2.0 * batch * m.layers * m.heads * m.head_dim * pairs
    head = 2.0 * batch * m.d_model * m.vocab           # last position only
    return proj + attn + head


def decode_flops(m: DenseDims, batch: int, cache_len: int) -> float:
    """One decode step: one new token per row against ``cache_len``
    cached positions (it attends to ``cache_len + 1``)."""
    proj = 2.0 * batch * _proj_macs_per_token(m)
    attn = 2.0 * 2.0 * batch * m.layers * m.heads * m.head_dim \
        * (cache_len + 1)
    head = 2.0 * batch * m.d_model * m.vocab
    return proj + attn + head


def decode_bytes(m: DenseDims, batch: int, cache_len: int,
                 dtype_bytes: int = BF16_BYTES) -> float:
    """Least bytes one decode step moves to and from HBM."""
    weights = m.layers * m.layer_params() + m.d_model        # + final norm
    weights += m.vocab * m.d_model                           # head table
    if not m.tied:
        weights += batch * m.d_model                         # looked-up rows
    kv_row = 2 * m.layers * m.kv_heads * m.head_dim          # k and v
    cache = batch * kv_row * (cache_len + 1)                 # read + write
    return float(dtype_bytes) * (weights + cache)
