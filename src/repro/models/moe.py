"""Mixture-of-experts MLP (llama4-style: top-1 routed + shared expert).

Dispatch is sort-free *rank-in-expert* scatter (the MaxText/MegaBlocks
pattern adapted to capacity buffers):

1. router picks top-k experts per token,
2. each token's *rank* within its expert is a cumsum over the one-hot
   dispatch matrix,
3. tokens scatter into an ``[E, C, d]`` capacity buffer (rank >= C drops —
   GShard-style capacity factor),
4. experts run as one batched einsum over the leading E dim (MXU-friendly),
5. results gather back by the same indices and are combined with the gate.

Sharding: the E dim of the buffer maps to the policy's ``experts`` axes
(expert parallelism); the expert ffn dim maps to ``expert_ff`` (TP inside
each expert). The scatter/gather lower to all-to-alls under GSPMD.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.sharding.policy import ShardingPolicy

Params = Dict[str, Any]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def init_moe(key, arch: ArchConfig, n_layers: int, dtype) -> Params:
    m = arch.moe
    d, fe, E = arch.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 7)
    sc_d, sc_f = d ** -0.5, fe ** -0.5

    def w(k, shape, sc):
        return (jax.random.normal(k, shape, jnp.float32) * sc).astype(dtype)

    p = {
        "moe_norm": jnp.zeros((n_layers, d), dtype),
        "router": w(ks[0], (n_layers, d, E), sc_d),
        "we_g": w(ks[1], (n_layers, E, d, fe), sc_d),
        "we_u": w(ks[2], (n_layers, E, d, fe), sc_d),
        "we_d": w(ks[3], (n_layers, E, fe, d), sc_f),
    }
    if m.shared_expert:
        p["ws_g"] = w(ks[4], (n_layers, d, fe), sc_d)
        p["ws_u"] = w(ks[5], (n_layers, d, fe), sc_d)
        p["ws_d"] = w(ks[6], (n_layers, fe, d), sc_f)
    return p


def moe_specs(arch: ArchConfig, policy: ShardingPolicy) -> Dict[str, Any]:
    sp = policy.spec
    p = {
        "moe_norm": sp("layers", None),
        "router": sp("layers", "embed", None),
        "we_g": sp("layers", "experts", "expert_embed", "expert_ff"),
        "we_u": sp("layers", "experts", "expert_embed", "expert_ff"),
        "we_d": sp("layers", "experts", "expert_ff", "expert_embed"),
    }
    if arch.moe.shared_expert:
        p["ws_g"] = sp("layers", "embed", "ff")
        p["ws_u"] = sp("layers", "embed", "ff")
        p["ws_d"] = sp("layers", "ff", "embed")
    return p


def moe_mlp(h: jax.Array, p: Params, arch: ArchConfig,
            policy: ShardingPolicy, dispatch: str = "grouped") -> jax.Array:
    """[B, S, d] -> [B, S, d]. Top-k routed experts (+ shared expert).

    ``dispatch='grouped'`` (default, perf iteration 2): routing, the
    rank-in-expert cumsum, and the capacity scatter all run PER BATCH ROW
    (GShard's group_size = one sequence), so every index is shard-local
    under batch sharding; the only inter-device movement is the clean
    [B,E,cap,d] → [E,B,cap,d] transpose (one all-to-all of exactly the
    buffer bytes).  ``dispatch='global'`` is the naive formulation whose
    global cumsum + scatter made GSPMD broadcast all token updates to all
    devices (~10 GiB/device/layer at scout prefill — EXPERIMENTS.md
    §Perf)."""
    m = arch.moe
    B, S, d = h.shape
    E, K = m.num_experts, m.experts_per_token
    hn = layers.rms_norm(h, p["moe_norm"], arch.norm_eps)
    if dispatch == "auto":
        # measured (EXPERIMENTS.md §Perf): with context-parallel attention
        # (seq sharded) the batch-grouped pin fights the seq sharding and
        # the global form is 2.7x cheaper on collectives; grouped wins
        # when tokens are batch-sharded only.
        dispatch = "global" if policy.rules.get("seq") else "grouped"
    if dispatch == "global" or B == 1:
        y = _dispatch_global(hn.reshape(B * S, d), p, arch, policy)
    else:
        y = _dispatch_grouped(hn, p, arch, policy)
    y = y.reshape(B, S, d)

    # --- shared expert -----------------------------------------------------
    if m.shared_expert:
        x = hn
        sg = jnp.einsum("bsd,df->bsf", x, p["ws_g"])
        su = jnp.einsum("bsd,df->bsf", x, p["ws_u"])
        sg = policy.pin(sg, "batch", "seq", "ff")
        sa = jax.nn.silu(sg) if arch.mlp_activation == "silu" else \
            jax.nn.gelu(sg, approximate=True)
        y = y + jnp.einsum("bsf,fd->bsd", sa * su, p["ws_d"])
    return y


def _route(x, p, m):
    """fp32 routing → (gate, idx) top-k over the last dim."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.experts_per_token)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate, idx


def _dispatch_global(x, p, arch, policy):
    """Naive single-group dispatch over N = B*S tokens."""
    m = arch.moe
    N, d = x.shape
    E, K = m.num_experts, m.experts_per_token
    gate, idx = _route(x, p, m)                       # [N, K]
    cap = _round_up(max(int(m.capacity_factor * K * N / E), 1), 8)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [N, K, E]
    flat = onehot.reshape(N * K, E)
    rank = jnp.cumsum(flat, axis=0) - flat
    rank = jnp.sum(rank * flat, axis=-1)              # [N*K]
    expert = idx.reshape(N * K)
    keep = rank < cap
    slot = jnp.where(keep, expert * cap + rank, E * cap)

    xk = jnp.repeat(x, K, axis=0)
    buf = jnp.zeros((E * cap + 1, d), x.dtype).at[slot].set(
        jnp.where(keep[:, None], xk, 0))
    xb = buf[: E * cap].reshape(E, cap, d)
    xb = policy.pin(xb, "experts", None, None)

    yb = _expert_ffn(xb, p, arch, policy)             # [E, cap, d]

    ybuf = jnp.concatenate(
        [yb.reshape(E * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)
    yk = ybuf[slot] * (keep * gate.reshape(N * K)).astype(x.dtype)[:, None]
    return jnp.sum(yk.reshape(N, K, d), axis=1)


def _dispatch_grouped(x, p, arch, policy):
    """Per-group dispatch: shard-local indices + one clean all-to-all.

    Groups are (batch row × seq shard): when the policy shards the
    sequence (context-parallel attention), tokens regroup as
    [B·ns, S/ns, d] so the rank cumsum and the capacity scatter stay
    WITHIN one device's shard; the only communication is the
    group-sharded → expert-sharded buffer transpose.

    x: [B, S, d] → y: [B, S, d]."""
    m = arch.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.experts_per_token
    # Groups are batch rows (G = B).  Grouping by seq shard as well would
    # keep context-parallel dispatch fully local, but the resulting
    # groups↔experts reshard hits GSPMD's involuntary-full-remat path
    # (XLA b/433785288) — ns stays 1 until a shard_map all-to-all island
    # replaces the transpose.
    ns = 1
    G, Sg = B * ns, S // ns
    xg = x.reshape(G, Sg, d)
    xg = policy.pin(xg, "batch", None, None)
    # barrier: keeps the (bf16) gather of seq-sharded tokens from being
    # convert-hoisted into fp32 by the fusing of the routing matmul
    xg = jax.lax.optimization_barrier(xg)

    gate, idx = _route(xg, p, m)                      # [G, Sg, K]
    cap = _round_up(max(int(m.capacity_factor * K * Sg / E), 1), 8)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [G, Sg, K, E]
    flat = onehot.reshape(G, Sg * K, E)
    rank = jnp.cumsum(flat, axis=1) - flat            # per-group prefix
    rank = jnp.sum(rank * flat, axis=-1)              # [G, Sg*K]
    expert = idx.reshape(G, Sg * K)
    keep = rank < cap
    slot = jnp.where(keep, expert * cap + rank, E * cap)   # [G, Sg*K]

    xk = jnp.repeat(xg, K, axis=1)                    # [G, Sg*K, d]
    rows = jnp.arange(G, dtype=jnp.int32)[:, None]
    buf = jnp.zeros((G, E * cap + 1, d), x.dtype).at[rows, slot].set(
        jnp.where(keep[..., None], xk, 0))
    xb = buf[:, : E * cap].reshape(G, E, cap, d)
    xb = policy.pin(xb, "token_groups", None, None, None)
    # the all-to-all: group-sharded → expert-sharded (G keeps its data
    # sharding so only the model/seq axis moves)
    xe = jnp.swapaxes(xb, 0, 1)                       # [E, G, cap, d]
    xe = policy.pin(xe, "experts", "token_groups_data", None, None)

    ye = _expert_ffn(xe, p, arch, policy)             # [E, G, cap, d]

    yb = jnp.swapaxes(ye, 0, 1)                       # [G, E, cap, d]
    yb = policy.pin(yb, "token_groups", None, None, None)
    ybuf = jnp.concatenate(
        [yb.reshape(G, E * cap, d), jnp.zeros((G, 1, d), x.dtype)], axis=1)
    yk = jnp.take_along_axis(ybuf, slot[..., None], axis=1)
    yk = yk * (keep * gate.reshape(G, Sg * K)).astype(x.dtype)[..., None]
    return jnp.sum(yk.reshape(G, Sg, K, d), axis=2).reshape(B, S, d)


def _expert_ffn(xb, p, arch, policy):
    """Batched expert MLP over the leading E dim.

    xb: [E, C, d] or [E, G, C, d] (extra dims fold into the row dim of
    the einsum via '...')."""
    g = jnp.einsum("e...d,edf->e...f", xb, p["we_g"])
    u = jnp.einsum("e...d,edf->e...f", xb, p["we_u"])
    if g.ndim == 3:
        g = policy.pin(g, "experts", None, "expert_ff")
    else:
        g = policy.pin(g, "experts", "token_groups_data", None, "expert_ff")
    act = jax.nn.silu(g) if arch.mlp_activation == "silu" else \
        jax.nn.gelu(g, approximate=True)
    yb = jnp.einsum("e...f,efd->e...d", act * u, p["we_d"])
    if yb.ndim == 3:
        return policy.pin(yb, "experts", None, None)
    return policy.pin(yb, "experts", "token_groups_data", None, None)


def moe_block_full(h, p, arch, policy, positions, attn_impl="jax",
                   dispatch="grouped"):
    """Attention + MoE MLP block (full-sequence mode)."""
    from repro.models import transformer as tfm
    a, kv = tfm.attention_full(h, p, arch, policy, positions, attn_impl)
    h = h + a
    h = h + moe_mlp(h, p, arch, policy, dispatch=dispatch)
    h = policy.pin(h, "batch", "seq", None)
    return h, kv


def moe_block_decode(h, p, arch, policy, k_cache, v_cache, cache_len,
                     cache_update: str = "onehot", dispatch="grouped"):
    from repro.models import transformer as tfm
    a, (k_cache, v_cache) = tfm.attention_decode(
        h, p, arch, policy, k_cache, v_cache, cache_len,
        cache_update=cache_update)
    h = h + a
    h = h + moe_mlp(h, p, arch, policy, dispatch=dispatch)
    return h, (k_cache, v_cache)
