"""Logical-axis sharding policy.

Model code never names mesh axes directly; it pins tensors by *logical*
axis names and the policy maps those to mesh axes with divisibility-safe
fallbacks.  This is what makes all 40 (arch x shape) cells lower on the
same code path:

* ``batch``     -> the data axes ('pod','data') when the global batch divides.
* ``qheads``    -> 'model' when H % tp == 0 (classic head TP) ...
* ``seq``       -> ... otherwise the sequence dim goes to 'model'
                  (context parallelism / megatron sequence parallelism).
* ``cache_seq`` -> 'model' (flash-decode: softmax over the sharded cache
                  lowers to all-reduces).
* ``ff`` / ``experts`` / ``vocab`` / ``ssm_pdim`` -> 'model' when divisible.
* weight "storage" dims (``embed`` on matmul inputs) -> data axes when
  training (FSDP/ZeRO-3 storage; GSPMD inserts the gathers).

A policy with ``mesh=None`` is a no-op (single-device smoke tests).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig

MeshAxes = Optional[Tuple[str, ...]]


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _divisible(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


@dataclass
class ShardingPolicy:
    mesh: Optional[Mesh]
    rules: Dict[str, MeshAxes] = field(default_factory=dict)
    attn_mode: str = "replicated"  # head_tp | context | replicated
    notes: Tuple[str, ...] = ()

    # -- mapping ---------------------------------------------------------
    def axes(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        return self.rules.get(logical)

    def spec(self, *logical: Optional[str]) -> P:
        """Map logical dims to mesh axes, de-duplicating: a mesh axis may
        appear at most once per spec (first dim wins — e.g. in context-
        parallel mode an activation pinned ('batch','seq','ff') keeps seq
        on 'model' and replicates ff; the weights keep ff sharding)."""
        used = set()
        out = []
        for l in logical:
            ax = self.axes(l)
            if ax is None:
                out.append(None)
                continue
            ax = tuple(a for a in ax if a not in used)
            used.update(ax)
            # bare name for a single axis, so a spec compares equal to
            # one written by hand as P('x') rather than P(('x',))
            out.append(None if not ax else ax[0] if len(ax) == 1 else ax)
        return P(*out)

    def named_sharding(self, *logical: Optional[str]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(*logical))

    def pin(self, x: jax.Array, *logical: Optional[str]) -> jax.Array:
        """with_sharding_constraint when a mesh is active, else identity.

        Shape-aware: a logical axis is only honored when the actual dim
        divides the mesh extent.  Without this, a decode-time pin of
        ('batch','seq','ff') on a [B,1,ff] tensor hands the model axis to
        the SIZE-1 seq dim, the de-dup then strips 'ff', and GSPMD
        resolves the conflict by all-gathering the weight matrices in
        fp32 — 2 GiB/step for a vocab projection (perf iteration 3)."""
        if self.mesh is None:
            return x
        used = set()
        axes = []
        for dim, l in zip(x.shape, logical):
            ax = self.rules.get(l) if l is not None else None
            if ax:
                ax = tuple(a for a in ax if a not in used)
            if ax:
                size = int(np.prod([_axis_size(self.mesh, a) for a in ax]))
                if size > 1 and dim % size == 0:
                    axes.append(ax)
                    used.update(ax)
                    continue
            axes.append(None)
        axes += [None] * (x.ndim - len(axes))
        return lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*axes)))

    @property
    def tp(self) -> int:
        return _axis_size(self.mesh, "model") if self.mesh else 1

    @property
    def seq_shards(self) -> int:
        """How many ways the sequence dim is sharded (context mode)."""
        if self.mesh is None or not self.rules.get("seq"):
            return 1
        import numpy as _np
        return int(_np.prod([_axis_size(self.mesh, a)
                             for a in self.rules["seq"]]))

    @property
    def data_parallel(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([_axis_size(self.mesh, a)
                            for a in ("pod", "data") if a in self.mesh.axis_names]))


def make_policy(
    arch: ArchConfig,
    shape: ShapeConfig,
    mesh: Optional[Mesh],
    *,
    training: bool = False,
    fsdp: Optional[bool] = None,
) -> ShardingPolicy:
    """Derive the logical->mesh mapping for one (arch, shape, mesh) cell."""
    if mesh is None:
        return ShardingPolicy(mesh=None)

    fsdp = training if fsdp is None else fsdp
    notes = []
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([_axis_size(mesh, a) for a in data_axes])) if data_axes else 1
    tp = _axis_size(mesh, "model")

    rules: Dict[str, MeshAxes] = {}

    # ---- batch ----------------------------------------------------------
    if data_axes and _divisible(shape.global_batch, dp):
        rules["batch"] = data_axes
    elif data_axes and len(data_axes) == 1 and _divisible(shape.global_batch,
                                                          _axis_size(mesh, data_axes[0])):
        rules["batch"] = data_axes
    else:
        # batch=1 long-context decode: replicate batch, note the idle axis
        rules["batch"] = None
        if shape.global_batch < dp:
            notes.append(f"batch={shape.global_batch} < dp={dp}: data axes idle")

    # ---- attention ------------------------------------------------------
    # Prefill prefers CONTEXT parallelism for GQA archs whose KV heads are
    # narrow: gathering k/v per layer (2·S·kv·hd bytes) beats head-TP's
    # two activation all-reduces (2·2·S·d bytes) whenever 2·kv·hd < d
    # (perf iteration 4 — deepseek prefill went 4x down on the collective
    # term; see EXPERIMENTS.md §Perf).
    seq = shape.seq_len
    prefer_context = (
        shape.kind == "prefill" and arch.num_heads
        and _divisible(seq, tp)
        and 2 * arch.num_kv_heads * arch.head_dim < arch.d_model)
    if (arch.num_heads and _divisible(arch.num_heads, tp)
            and not prefer_context):
        attn_mode = "head_tp"
        rules["qheads"] = ("model",)
        rules["kvheads"] = ("model",) if _divisible(arch.num_kv_heads, tp) else None
        rules["seq"] = None
    elif _divisible(seq, tp):
        attn_mode = "context"
        rules["qheads"] = None
        rules["kvheads"] = None
        rules["seq"] = ("model",)
        if arch.num_heads:
            notes.append(
                f"H={arch.num_heads} % tp={tp} != 0: context-parallel attention")
    else:
        attn_mode = "replicated"
        rules["qheads"] = None
        rules["kvheads"] = None
        rules["seq"] = None
        notes.append("attention replicated over model axis")

    # decode-time KV cache: shard the sequence dim (flash-decode pattern)
    rules["cache_seq"] = ("model",) if _divisible(seq, tp) else None
    # In non-head_tp modes attention *weights* still need a model-axis
    # storage shard (otherwise 15/16 of the axis holds replicas); hd is a
    # pure storage dim there — GSPMD gathers it transiently at use.
    if attn_mode != "head_tp" and arch.num_heads and _divisible(arch.head_dim, tp):
        rules["head_dim"] = ("model",)
    else:
        rules["head_dim"] = None

    # ---- mlp / vocab ----------------------------------------------------
    rules["ff"] = ("model",) if _divisible(arch.d_ff or 0, tp) else None
    rules["vocab"] = ("model",) if _divisible(arch.vocab_size, tp) else None
    if rules["vocab"] is None:
        notes.append(f"vocab={arch.vocab_size} % tp={tp} != 0: vocab replicated")

    # token groups for the MoE grouped dispatch: whatever axes shard the
    # (batch × seq-chunk) token space — keeps every dispatch index local
    rules["token_groups"] = tuple(
        (data_axes or ()) + (("model",) if rules.get("seq") else ())) or None

    # ---- MoE ------------------------------------------------------------
    if arch.moe is not None:
        E = arch.moe.num_experts
        ff_tp = _divisible(arch.moe.d_ff_expert, tp)
        ff_dp = _divisible(arch.moe.d_ff_expert, dp) if data_axes else False
        # Preference order maximizes weight sharding:
        #   EP over ('pod','data') + ff TP  >  EP over ('data',) + ff TP
        #   >  EP over 'model'  >  replicated experts + ff TP.
        # (An EP-over-'model' layout for context-parallel prefill would
        # make the dispatch transpose a clean model-axis all-to-all, but
        # GSPMD currently full-rematerializes that reshard — XLA
        # b/433785288; revisit with a shard_map all-to-all island.)
        ep_axes = None
        for cand in (data_axes, data_axes[-1:] if data_axes else None):
            if cand and _divisible(E, int(np.prod([_axis_size(mesh, a)
                                                   for a in cand]))):
                ep_axes = tuple(cand)
                break
        if ep_axes and ff_tp:
            rules["experts"] = ep_axes
            rules["expert_ff"] = ("model",)
            notes.append(f"E={E}: expert-parallel over {ep_axes}, "
                         "expert ff TP")
        elif _divisible(E, tp):
            rules["experts"] = ("model",)
            rules["expert_ff"] = None
        else:
            rules["experts"] = None
            rules["expert_ff"] = ("model",) if ff_tp else None
            notes.append(f"E={E}: experts replicated")
    rules["token_groups_data"] = data_axes or None

    # ---- SSM -------------------------------------------------------------
    if arch.ssm is not None:
        nh = arch.ssm.num_heads(arch.d_model)
        if _divisible(nh, tp):
            rules["ssm_heads"] = ("model",)
            rules["ssm_pdim"] = None
        elif _divisible(arch.ssm.head_dim, tp):
            rules["ssm_heads"] = None
            rules["ssm_pdim"] = ("model",)
            notes.append(f"ssm heads={nh} % tp={tp} != 0: shard head_dim")
        else:
            rules["ssm_heads"] = None
            rules["ssm_pdim"] = None
            notes.append("ssm replicated over model axis")
        rules["ssm_state"] = None

    # ---- weight storage (FSDP / ZeRO-3) ----------------------------------
    # Serving also storage-shards weights over the data axes when the
    # TP(+EP)-sharded copy plus the decode KV cache would not fit a
    # 16 GiB v5e — ZeRO-style weight streaming; GSPMD inserts the
    # per-layer gathers.  The fit estimate accounts for expert
    # parallelism: EP-sharded expert weights don't burden the TP quota
    # (perf iteration 1 — the old total/tp heuristic falsely streamed
    # scout/deepseek prefill weights and paid an fp32 data-axis
    # all-reduce per layer; see EXPERIMENTS.md §Perf).
    total_params, _ = arch.param_count()
    dense_params = total_params
    if arch.moe is not None and rules.get("experts"):
        ep = int(np.prod([_axis_size(mesh, a) for a in rules["experts"]]))
        ff_shard = tp if rules.get("expert_ff") else 1
        n_moe = arch.num_layers // arch.moe.moe_every
        expert_only = (arch.moe.num_experts * 3 * arch.d_model
                       * arch.moe.d_ff_expert) * n_moe
        dense_params = total_params - expert_only
        expert_gb = expert_only * 2 / (ep * ff_shard) / 2 ** 30
    else:
        expert_gb = 0.0
    weight_gb_per_chip = dense_params * 2 / max(tp, 1) / 2 ** 30 + expert_gb
    cache_gb = 0.0
    if shape.kind == "decode":
        from repro.models.kvcache import cache_bytes
        shards = tp * (dp if _divisible(shape.global_batch, dp) else 1)
        cache_gb = cache_bytes(arch, shape.global_batch,
                               shape.seq_len) / shards / 2 ** 30
    if data_axes and _divisible(arch.d_model, dp) and (
            fsdp or weight_gb_per_chip + cache_gb > 12.0):
        rules["embed"] = data_axes
        if not fsdp:
            notes.append(
                f"weights {weight_gb_per_chip:.1f} + cache {cache_gb:.1f} "
                "GiB/chip under TP alone: storage-sharded over data axes "
                "(ZeRO-style)")
    else:
        rules["embed"] = None

    # expert weights' d_model dim: use whatever data axes the experts
    # themselves don't occupy (avoids a duplicate-axis PartitionSpec).
    if arch.moe is not None:
        used = rules.get("experts") or ()
        free = tuple(a for a in (rules["embed"] or ()) if a not in used)
        rules["expert_embed"] = free or None

    rules["layers"] = None

    return ShardingPolicy(mesh=mesh, rules=rules, attn_mode=attn_mode,
                          notes=tuple(notes))
