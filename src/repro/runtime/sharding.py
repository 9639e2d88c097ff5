"""Sharding plan: one object that tells every layer how to place tensors.

Axes convention (the production mesh of launch/mesh.py):
  * `pod`   — slow inter-pod axis (DCI): pure data parallelism + the axis
              the CEAZ-compressed gradient reduction runs over.
  * `data`  — intra-pod data parallelism; also hosts ZeRO-1 optimizer-state
              sharding and context parallelism for long sequences.
  * `model` — tensor parallelism: attention heads, FFN hidden, vocab,
              MoE experts (EP), and the KV-cache sequence dim at decode.

A plan with mesh=None degrades every helper to a no-op so the exact same
model code runs single-device in unit tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



@dataclasses.dataclass
class ShardingPlan:
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ("data",)   # ('pod','data') when multi-pod
    model_axis: str = "model"
    # axis used for ZeRO/FSDP extra param sharding and context parallelism
    zero_axis: str = "data"
    # TP placement for attention activations/weights: shard the heads dim
    # when n_heads % model_size == 0, else shard head_dim (gemma3: 8 or 4
    # heads < 16-way model axis, but head_dim=256 divides fine)
    attn_part: str = "heads"                  # 'heads' | 'head_dim'
    # decode cache layout: wide=True shards the cache SEQUENCE dim over
    # (batch axes + model) and leaves batch unsharded — used when
    # global_batch < DP size (long_500k). In-model constraints MUST agree
    # with the input layout or XLA reshards the cache every layer.
    decode_wide: bool = False

    def cache_kv_spec(self):
        """(batch, seq, ...) spec parts for decode caches."""
        if self.decode_wide:
            return None, tuple(self.batch_axes) + (self.model_axis,)
        return self.batch, self.model_axis

    # -- helpers -------------------------------------------------------------
    @property
    def batch(self):
        return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]

    def axis_size(self, name: str) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[name]

    @property
    def model_size(self) -> int:
        return self.axis_size(self.model_axis) if self.mesh else 1

    def spec(self, *parts) -> P:
        return P(*parts)

    def cs(self, x, *parts):
        """with_sharding_constraint if a mesh is active, else identity."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*parts)))

    def named(self, *parts) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*parts))

    # activation conventions ---------------------------------------------------
    def act_btd(self, x):
        """(batch, seq, d_model): batch over DP axes, d replicated."""
        return self.cs(x, self.batch, None, None)

    def act_bthd(self, x):
        """(batch, seq, heads, head_dim): TP over heads or head_dim."""
        if self.attn_part == "heads":
            return self.cs(x, self.batch, None, self.model_axis, None)
        return self.cs(x, self.batch, None, None, self.model_axis)

    def act_btf(self, x):
        """(batch, seq, ffn_hidden): hidden over model axis."""
        return self.cs(x, self.batch, None, self.model_axis)

    def logits_btv(self, x):
        """(batch, seq, vocab): vocab over model axis."""
        return self.cs(x, self.batch, None, self.model_axis)


def shard_compress(x: np.ndarray, plan: ShardingPlan,
                   eb_rel: float = 1e-4, chunk_values: int = 1 << 20,
                   block_size: int = 4096):
    """Shard-parallel fused compression of one large array.

    Cuts `x` along its leading axis into one shard per device of the
    plan's batch axes (falling back to a single shard without a mesh)
    and compresses them all through one pair of fused device passes —
    each shard is an independent CEAZ stream, so ranks can decode in
    parallel. Returns (compressed_list, shard_len) where shard_len is
    the leading-axis extent of every shard but possibly the last.

    Mesh parallelism needs the shard count to divide the batch axes'
    device count; a ragged tail degrades that batch (and the tail) to
    unsharded fused passes — correct, just not device-parallel.
    """
    from . import fused
    if x.shape[0] == 0:
        raise ValueError("shard_compress needs a non-empty leading axis")
    n_dev = int(np.prod([plan.axis_size(a) for a in plan.batch_axes])) \
        if plan.mesh is not None else 1
    n_dev = max(1, min(n_dev, x.shape[0]))
    per = -(-x.shape[0] // n_dev)
    shards = [x[s:s + per] for s in range(0, x.shape[0], per)]
    if len({s.shape for s in shards}) > 1:      # ragged tail: pad-free split
        head, tail = shards[:-1], shards[-1:]
        comps = (fused.batch_compress(head, eb_rel, chunk_values,
                                      block_size, plan=plan)
                 + fused.batch_compress(tail, eb_rel, chunk_values,
                                        block_size, plan=None))
    else:
        comps = fused.batch_compress(shards, eb_rel, chunk_values,
                                     block_size, plan=plan)
    return comps, per


def make_plan(mesh: Optional[Mesh]) -> ShardingPlan:
    if mesh is None:
        return ShardingPlan(mesh=None)
    axes = tuple(mesh.axis_names)
    batch_axes = tuple(a for a in ("pod", "data") if a in axes) or (axes[0],)
    return ShardingPlan(mesh=mesh, batch_axes=batch_axes)


# ---------------------------------------------------------------------------
# Parameter sharding rules: map param-tree paths to PartitionSpecs.
# Conventions used by models/* param builders:
#   names ending in
#     'emb'      -> (vocab=model, d=None)
#     'wq','wkv_b','wo' etc: see table below
# We instead key on array *shape roles* recorded by the builders: each leaf
# is a plain array; the builders attach specs through `PARAM_SPECS` name
# patterns (path substring -> spec parts relative to axes).
# ---------------------------------------------------------------------------

PARAM_RULES: Sequence[Tuple[str, Tuple]] = (
    # (path substring, partition parts) — first match wins. None = replicate.
    # 'ATTN'/'ATTN_T' resolve per plan.attn_part (heads vs head_dim TP).
    ("embed/table", ("model", None)),           # vocab-sharded embeddings
    ("attn/wq", (None, "ATTN_H", "ATTN_D")),    # (d, heads, head_dim)
    ("attn/wk", (None, "ATTN_H", "ATTN_D")),
    ("attn/wv", (None, "ATTN_H", "ATTN_D")),
    ("attn/wo", ("ATTN_H", "ATTN_D", None)),    # (heads, head_dim, d)
    ("mla/wq_a", (None, None)),
    ("mla/wq_b", (None, "model", None)),
    ("mla/wkv_a", (None, None)),
    ("mla/wkv_b", (None, "model", None)),
    ("mla/wo", ("model", None, None)),
    ("mlp/wi", (None, "model")),                # (d, ff)
    ("mlp/wg", (None, "model")),
    ("mlp/wo", ("model", None)),                # (ff, d)
    ("moe/router", (None, None)),
    # experts: EP over model + FSDP over data (gathered per layer in the
    # scan; without the data factor DeepSeek-236B cannot fit 16 GB/chip)
    ("moe/wi", ("model", "data", None)),        # (E, d, ff)
    ("moe/wg", ("model", "data", None)),
    ("moe/wo", ("model", "data", None)),        # (E, ff, d)
    ("ssm/wi_z", (None, "model")),              # mamba z/x: col-parallel
    ("ssm/wi_x", (None, "model")),
    ("ssm/wi_", (None, None)),                  # B/C/dt streams: replicated
    ("ssm/wi", (None, "model")),                # rwkv-style fused in-proj
    ("ssm/wo", ("model", None)),                # mamba/rwkv out-proj (row)
    ("ssm/conv_x_w", (None, "model")),
    ("ssm/conv_x_b", ("model",)),
    ("ssm/conv", (None, None)),                 # B/C convs: replicated
    ("ssm/wr", (None, "model")),                # rwkv projections
    ("ssm/wk", (None, "model")),
    ("ssm/wv", (None, "model")),
    ("ssm/wg", (None, "model")),
    ("ssm_cmix/wk", (None, "model")),
    ("ssm_cmix/wv", ("model", None)),
    ("ssm_cmix/wr", (None, "model")),
    ("ssm/", (None,)),                          # other ssm leaves: replicate
    ("norm", (None,)),
    ("", (None,)),                              # default: replicate
)


def _resolve(parts, attn_part: str):
    out = []
    for p in parts:
        if p == "ATTN_H":
            out.append("model" if attn_part == "heads" else None)
        elif p == "ATTN_D":
            out.append("model" if attn_part == "head_dim" else None)
        else:
            out.append(p)
    return tuple(out)


def spec_for_path(path: str, ndim: int, attn_part: str = "heads") -> P:
    for pat, parts in PARAM_RULES:
        if pat in path:
            parts = _resolve(parts, attn_part)
            if len(parts) < ndim:           # stacked (scanned) leading dims
                parts = (None,) * (ndim - len(parts)) + parts
            elif len(parts) > ndim:
                parts = parts[-ndim:] if ndim else ()
            return P(*parts)
    return P(*([None] * ndim))


def leaf_sharding(path: str, shape, plan: ShardingPlan):
    """NamedSharding for ONE leaf by PARAM_RULES path match, or None when
    the plan has no mesh. Needs only the flat key path and shape, so a
    streaming restore can place each leaf as it decodes — before the full
    tree exists."""
    if plan.mesh is None:
        return None
    shape = tuple(shape)
    spec = spec_for_path(path, len(shape), plan.attn_part)
    # divisibility guard: pjit argument shardings must divide evenly
    # (e.g. GQA kv-heads=2 cannot shard over a 16-way model axis) —
    # non-divisible dims fall back to replication.
    parts = []
    for i, p in enumerate(spec):
        if p is None:
            parts.append(None)
            continue
        axes = p if isinstance(p, tuple) else (p,)
        size = int(np.prod([plan.mesh.shape[a] for a in axes]))
        parts.append(p if shape[i] % size == 0 else None)
    return NamedSharding(plan.mesh, P(*parts))


def param_shardings(params, plan: ShardingPlan):
    """Pytree of NamedShardings matching `params` via PARAM_RULES."""
    if plan.mesh is None:
        return jax.tree.map(lambda _: None, params)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf_sharding(
            jax.tree_util.keystr(path, simple=True, separator="/"),
            getattr(leaf, "shape", ()), plan),
        params)
