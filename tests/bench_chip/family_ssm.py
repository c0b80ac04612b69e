"""The attention-free Mamba2 family, as the repo's ``models/ssm.py``
computes it: a second family for the chip benchmark, brought as a file.

The test of a family that arrives as files alone copies this module to
``benchmarks/chip/families/ssm.py`` of a checkout; it follows the contract
of ``benchmarks/chip/families/__init__.py``.

**Dims** are read from the Hugging Face ``Mamba2Config`` keys
(``hidden_size``, ``num_hidden_layers``, ``vocab_size``, ``num_heads``,
``head_dim``, ``state_size``, ``conv_kernel``, ``chunk_size``,
``layer_norm_epsilon``, ``tie_word_embeddings``).

**Weights.** The tree is the layout of the repo's ``init_ssm`` (``embed``,
``final_norm``, ``lm_head`` when untied, and ``blocks`` with a leading
layer axis), ``A_log`` and ``dt_bias`` in float32 as the program keeps
them, the rest in bfloat16. Norm offsets, ``dt_bias``, ``A_log`` and ``D``
are drawn about values that keep the decay of the state between 0 and 1,
so the comparison exercises every leaf.

**Reference.** Straight ``jax.numpy`` at ``Precision.HIGHEST``, float32,
layer by layer: per layer a pre-norm, the projections, a depthwise causal
convolution written as its sum, and the selective state recurrence run
token by token (``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``,
``y_t = C_t h_t + D x_t``), not the chunked dual form the program uses.
Departures of the repo's Mamba2 from the published one (arXiv:2405.21060,
``state-spaces/mamba2-130m``) that the reference copies:

- one ``in_proj`` is five matrices ``wz``, ``wx``, ``wB``, ``wC``, ``wdt``,
  and the convolution over ``x``, ``B``, ``C`` three depthwise ones (a
  re-parametrisation, same function);
- the convolution has no bias (published ``use_conv_bias`` true);
- the gated RMSNorm normalises each head's ``head_dim`` values, where the
  published one with ``n_groups`` 1 normalises the whole ``d_inner``;
- norm weights are offsets applied as ``x * (1 + w)``.

``quant`` rounds every projection and the head as the dense family's
control does.

**Work.** Per token and layer: two operations per multiply-add of the
projections, the output projection and the convolution taps, and two
multiply-adds per element of the state (decay and input, read-out), the
recurrence's own work, not the chunked form's; the head at the positions
whose logits the step returns. Decode bytes: every weight read once, the
state (float32) read and written, the convolution's tail read and one new
position of it written.
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

NORM_SCALE = 0.1
EMBED_SCALE = 0.02
BF16_BYTES = 2
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SSMDims:
    """The shape of an attention-free Mamba2 model."""
    name: str
    vocab: int
    d_model: int
    layers: int
    heads: int
    head_dim: int
    d_state: int
    conv_width: int
    chunk_size: int
    tied: bool
    norm_eps: float

    @classmethod
    def from_keys(cls, name: str, keys: Mapping[str, Any]) -> "SSMDims":
        return cls(
            name=name,
            vocab=int(keys["vocab_size"]),
            d_model=int(keys["hidden_size"]),
            layers=int(keys["num_hidden_layers"]),
            heads=int(keys["num_heads"]),
            head_dim=int(keys["head_dim"]),
            d_state=int(keys["state_size"]),
            conv_width=int(keys["conv_kernel"]),
            chunk_size=int(keys["chunk_size"]),
            tied=bool(keys["tie_word_embeddings"]),
            norm_eps=float(keys["layer_norm_epsilon"]))

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    def layer_leaves(self) -> Dict[str, Tuple[Tuple[int, ...], float, float,
                                              Any]]:
        """Per-layer leaf -> (shape without the layer axis, scale, offset,
        dtype)."""
        d, nh, hd, ds, cw = (self.d_model, self.heads, self.head_dim,
                             self.d_state, self.conv_width)
        bf = W.SERVED_DTYPE
        return {
            "ssm_norm": ((d,), NORM_SCALE, 0.0, bf),
            "wz": ((d, nh, hd), d ** -0.5, 0.0, bf),
            "wx": ((d, nh, hd), d ** -0.5, 0.0, bf),
            "wB": ((d, ds), d ** -0.5, 0.0, bf),
            "wC": ((d, ds), d ** -0.5, 0.0, bf),
            "wdt": ((d, nh), d ** -0.5, 0.0, bf),
            "conv_x": ((cw, nh, hd), cw ** -0.5, 0.0, bf),
            "conv_B": ((cw, ds), cw ** -0.5, 0.0, bf),
            "conv_C": ((cw, ds), cw ** -0.5, 0.0, bf),
            # A = -exp(A_log) about -e; dt = softplus(. - 2) about 0.13
            "A_log": ((nh,), 0.5, 1.0, F32),
            "dt_bias": ((nh,), 0.5, -2.0, F32),
            "D": ((nh,), 0.5, 1.0, bf),
            "gate_norm": ((nh, hd), NORM_SCALE, 0.0, bf),
            "wo": ((nh, hd, d), self.d_inner ** -0.5, 0.0, bf),
        }

    def layer_bytes(self) -> int:
        return sum(int(np.prod(s)) * jnp.dtype(t).itemsize
                   for s, _, _, t in self.layer_leaves().values())


Dims = SSMDims


def repo_arch(m: SSMDims, config: Mapping[str, Any]):
    """The config's ``repo_arch`` (an ``ssm`` family arch) at the config's
    sizes, named ``m.name``."""
    from repro.configs import ARCHS
    base = ARCHS[config["repo_arch"]]
    if base.family != "ssm":
        raise ValueError(f"repo_arch {base.name} is not of the ssm family")
    if int(config.get("n_groups", 1)) != 1:
        raise ValueError("the repo's Mamba2 has one group of B and C")
    if config.get("use_conv_bias") or config.get("use_bias"):
        raise ValueError("the repo's Mamba2 has no biases")
    if m.d_inner % m.d_model:
        raise ValueError("num_heads * head_dim is not a multiple of "
                         "hidden_size")
    ssm = dataclasses.replace(
        base.ssm, d_state=m.d_state, head_dim=m.head_dim,
        expand=m.d_inner // m.d_model, conv_width=m.conv_width,
        chunk_size=m.chunk_size)
    return dataclasses.replace(
        base, name=m.name, num_layers=m.layers, d_model=m.d_model,
        vocab_size=m.vocab, tie_embeddings=m.tied, norm_eps=m.norm_eps,
        ssm=ssm)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_LEAF_INDEX = {n: i for i, n in enumerate(
    ("embed", "final_norm", "lm_head", "ssm_norm", "wz", "wx", "wB", "wC",
     "wdt", "conv_x", "conv_B", "conv_C", "A_log", "dt_bias", "D",
     "gate_norm", "wo"))}


def _leaf_key(words: jax.Array, name: str) -> jax.Array:
    return W.leaf_key(words, _LEAF_INDEX[name])


def _layer(m: SSMDims, words: jax.Array, layer: jax.Array
           ) -> Dict[str, jax.Array]:
    return {n: (W.draw(jax.random.fold_in(_leaf_key(words, n), layer), s,
                       sc, F32) + off).astype(dt)
            for n, (s, sc, off, dt) in m.layer_leaves().items()}


@partial(jax.jit, static_argnums=0)
def layer_weights(m: SSMDims, words: jax.Array, layer: jax.Array
                  ) -> Dict[str, jax.Array]:
    """One layer's leaves, equal to that layer of :func:`served_params`."""
    return _layer(m, words, layer)


@partial(jax.jit, static_argnums=0)
def table_weights(m: SSMDims, words: jax.Array) -> Dict[str, jax.Array]:
    d, V = m.d_model, m.vocab
    out = {"embed": W.draw(_leaf_key(words, "embed"), (V, d), EMBED_SCALE),
           "final_norm": W.draw(_leaf_key(words, "final_norm"), (d,),
                                NORM_SCALE)}
    if not m.tied:
        out["lm_head"] = W.draw(_leaf_key(words, "lm_head"), (d, V),
                                d ** -0.5)
    return out


@partial(jax.jit, static_argnums=0)
def served_params(m: SSMDims, words: jax.Array) -> Dict[str, Any]:
    layers = jnp.arange(m.layers, dtype=jnp.uint32)
    blocks = jax.vmap(lambda l: _layer(m, words, l))(layers)
    return {**table_weights(m, words), "blocks": blocks}


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time: ``out[t] = sum_i w[i] *
    x[t - (cw - 1) + i]``, zeros before the first token. x: [B, T, ...];
    w: [cw, ...]."""
    cw, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(xp[:, i:i + T] * w[i] for i in range(cw))


@partial(jax.jit, static_argnames=("m", "quant"))
def block(h: jax.Array, p: Dict[str, jax.Array], *, m: SSMDims,
          quant: Optional[str]) -> jax.Array:
    """One Mamba2 layer over the whole sequence, the state carried token
    by token."""
    p = jax.tree.map(lambda a: a.astype(F32), p)
    u = rms_norm(h, p["ssm_norm"], m.norm_eps)
    z = P.mm("btd,dhp->bthp", u, p["wz"], (2,), (0,), quant)
    x = P.mm("btd,dhp->bthp", u, p["wx"], (2,), (0,), quant)
    b = P.mm("btd,dn->btn", u, p["wB"], (2,), (0,), quant)
    c = P.mm("btd,dn->btn", u, p["wC"], (2,), (0,), quant)
    dt = P.mm("btd,dh->bth", u, p["wdt"], (2,), (0,), quant)
    x = jax.nn.silu(causal_conv(x, p["conv_x"]))
    b = jax.nn.silu(causal_conv(b, p["conv_B"]))
    c = jax.nn.silu(causal_conv(c, p["conv_C"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def step(state, t):
        x_t, dt_t, b_t, c_t = t               # [B,h,p] [B,h] [B,n] [B,n]
        state = state * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", x_t * dt_t[..., None], b_t, precision=P.HI)
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t,
                                 precision=P.HI)

    state0 = jnp.zeros((h.shape[0], m.heads, m.head_dim, m.d_state), F32)
    per_t = [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(step, state0, per_t)
    y = jnp.moveaxis(y, 0, 1) + x * p["D"][:, None]
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["gate_norm"], m.norm_eps)          # per head
    return h + P.mm("bthp,hpd->btd", y, p["wo"], (2, 3), (0, 1), quant)


@jax.jit
def _embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(h: jax.Array, tables: Dict[str, jax.Array], *, m: SSMDims,
          quant: Optional[str]) -> jax.Array:
    x = rms_norm(h, tables["final_norm"].astype(F32), m.norm_eps)
    table = (tables["embed"].T if m.tied else tables["lm_head"]).astype(F32)
    return P.mm("btd,dv->btv", x, table, (2,), (0,), quant)


def logits(m: SSMDims, words: np.ndarray, tokens: np.ndarray, first: int,
           quant: Optional[str] = None) -> jax.Array:
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
def _macs_per_token(m: SSMDims) -> int:
    d, nh, ds, di = m.d_model, m.heads, m.d_state, m.d_inner
    proj = d * (2 * di + 2 * ds + nh) + di * d
    conv = m.conv_width * (di + 2 * ds)
    state = 2 * di * ds
    return m.layers * (proj + conv + state)


def prefill_flops(m: SSMDims, batch: int, seq: int) -> float:
    return 2.0 * batch * (seq * _macs_per_token(m) + m.d_model * m.vocab)


def decode_flops(m: SSMDims, batch: int, cache_len: int) -> float:
    return 2.0 * batch * (_macs_per_token(m) + m.d_model * m.vocab)


def decode_bytes(m: SSMDims, batch: int, cache_len: int) -> float:
    weights = m.layers * m.layer_bytes()
    weights += BF16_BYTES * (m.d_model + m.vocab * m.d_model)
    if not m.tied:
        weights += BF16_BYTES * batch * m.d_model
    state = 2 * 4 * m.heads * m.head_dim * m.d_state        # read + write
    tail = BF16_BYTES * m.conv_width * (m.d_inner + 2 * m.d_state)
    return float(weights + batch * m.layers * (state + tail))
