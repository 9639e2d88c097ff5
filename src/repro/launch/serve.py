"""Serving entry points: jit'd prefill and decode with cache shardings.

Decode-time placement: KV/cache SEQUENCE dims are sharded over the model
axis (context parallelism — a 32k/500k cache never fits replicated), batch
over the DP axes; SSM states shard heads over model. For the long_500k
cell (batch=1 < DP size) the cache sequence shards over (data, model)
jointly and batch stays replicated — all 256 chips hold context slices.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..runtime.sharding import ShardingPlan


def _seq_axes(plan: ShardingPlan, wide: bool):
    """Axis (tuple) for cache sequence dims."""
    if wide:
        return tuple(plan.batch_axes) + (plan.model_axis,)
    return plan.model_axis


def cache_shardings(cache, plan: ShardingPlan, batch_sharded: bool = True):
    """Pytree of NamedShardings for a serve cache (see module docstring)."""
    if plan.mesh is None:
        return jax.tree.map(lambda _: None, cache)
    wide = not batch_sharded
    seq_ax = _seq_axes(plan, wide)
    bat = plan.batch if batch_sharded else None
    msize = plan.model_size

    def leaf_spec(path, leaf) -> P:
        keys = jax.tree_util.keystr(path, simple=True, separator="/")
        nd = len(leaf.shape)
        name = keys.split("/")[-1]
        shape = leaf.shape
        if name == "pos":
            return P()
        if name in ("k", "v"):               # (R, B, L, K, D)
            L = shape[-3]
            dp = int(np.prod([plan.axis_size(a) for a in plan.batch_axes]))
            parts = [None] * nd
            parts[-4] = bat
            if wide and L % (msize * dp) == 0:
                parts[-3] = tuple(plan.batch_axes) + (plan.model_axis,)
            elif L % msize == 0:
                parts[-3] = plan.model_axis
            return P(*parts)
        if name in ("xk", "xv"):             # (R, B, F, K, D) cross-attn
            parts = [None] * nd
            parts[-4] = bat
            return P(*parts)
        if name in ("c_kv", "k_rope"):       # (R, B, S, c)
            parts = [None] * nd
            parts[-3] = bat
            S = shape[-2]
            if S % msize == 0:
                parts[-2] = plan.model_axis
            return P(*parts)
        if name == "conv":                   # (R, B, K-1, C)
            parts = [None] * nd
            parts[-3] = bat
            if shape[-1] % msize == 0:
                parts[-1] = plan.model_axis
            return P(*parts)
        if name == "state":                  # (R, B, H, P, N|P)
            parts = [None] * nd
            parts[-4] = bat
            if shape[-3] % msize == 0:
                parts[-3] = plan.model_axis
            return P(*parts)
        if name in ("sx", "sx_cmix"):        # (R, B, d)
            parts = [None] * nd
            parts[-2] = bat
            return P(*parts)
        parts = [None] * nd
        return P(*parts)

    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(plan.mesh, leaf_spec(p, l)), cache)


def _serving_cast(dtype):
    """Per-leaf host-side cast to the serving dtype: applied BEFORE
    device placement so only one leaf ever exists in both precisions —
    startup peak HBM is the serving (bf16) footprint, not f32+bf16."""
    np_dtype = np.dtype(dtype)

    def cast(key, arr):
        if isinstance(arr, np.ndarray) \
                and jnp.issubdtype(arr.dtype, jnp.floating) \
                and arr.dtype != np_dtype:
            return arr.astype(np_dtype)
        return arr
    return cast


def _serving_step_dir(directory: str, step: Optional[int]):
    """(step_dir, step) of the newest usable checkpoint (or `step`)."""
    from ..checkpoint import ckpt as C
    steps = C.available_steps(directory)
    if step is not None:
        steps = [s for s in steps if s == step]
    if not steps:
        return None
    s = steps[-1]
    return os.path.join(directory, f"step_{s:08d}"), s


def restore_serving_params(directory: str, plan: ShardingPlan,
                           step: Optional[int] = None, ckpt_cfg=None,
                           dtype=jnp.bfloat16, paged: bool = False,
                           **paged_kw):
    """Startup restore for serving: checkpoint leaf stream -> engine-fed
    fused decode -> serving-dtype cast -> placement on the serve mesh.

    Leaf records stream through the read engine (prefetch thread +
    batched fused device decode, no host-numpy decode bounce); the cast
    to `dtype` (bf16 by default: serving re-reading f32 masters doubles
    parameter HBM traffic, see `serving_params_struct`) is fused into
    the per-leaf decode->placement path, and every leaf is placed with
    its PARAM_RULES sharding as it decodes — the serve mesh may differ
    arbitrarily from the training mesh.

    With `paged=True` the full restore is skipped entirely: returns
    ``(PagedParamStore, meta)`` — the compressed stream stays resident
    and layers decode on first touch (see `paged_serving_store`, which
    also takes `paged_kw` like ``cache_bytes``). Otherwise returns
    (params, meta). None when no usable checkpoint exists.
    """
    if paged:
        return paged_serving_store(directory, plan, step=step,
                                   ckpt_cfg=ckpt_cfg, dtype=dtype,
                                   **paged_kw)
    from ..checkpoint import ckpt as C
    restored = C.restore_checkpoint(directory, step=step, plan=plan,
                                    cfg=ckpt_cfg,
                                    leaf_transform=_serving_cast(dtype))
    if restored is None:
        return None
    state, meta = restored
    params = (state["params"] if isinstance(state, dict)
              and "params" in state else state)
    # mesh-less restores stay host-side numpy through the transform
    # path; normalize to jax arrays (already serving dtype — no second
    # full-precision materialization)
    return jax.tree.map(jnp.asarray, params), meta


def paged_serving_store(directory: str, plan: ShardingPlan,
                        step: Optional[int] = None, ckpt_cfg=None,
                        dtype=jnp.bfloat16, **paged_kw):
    """Open the newest usable checkpoint as a decode-on-demand
    :class:`~repro.serve.paging.PagedParamStore` (compressed-resident
    weights; layers decode on first touch with the serving-dtype cast
    and PARAM_RULES placement fused in). Extra `paged_kw` forward to
    the store (``cache_bytes``, ``group``, ...).

    Returns (store, meta) or None when no usable checkpoint exists
    (corruption is an OSError; any other error propagates).
    The store's decode facade mirrors `restore_checkpoint`'s compressor
    config, so paged leaves are bit-identical to a full restore.
    """
    from ..checkpoint import ckpt as C
    from ..serve.paging import PagedParamStore
    found = _serving_step_dir(directory, step)
    if found is None:
        return None
    d, s = found
    try:
        manifest = C.read_manifest(d)
        if manifest.get("format", 1) < 2:
            print(f"checkpoint {d} has no format-2 leaf stream to page")
            return None
        stream = os.path.join(d, manifest.get("file", C.LEAVES_STREAM))
        cfg = ckpt_cfg or C.CheckpointConfig()
        prefix = "params/" if any(
            k.startswith("params/") for k in manifest["leaves"]) else None
        store = PagedParamStore(stream, plan=plan, dtype=dtype,
                                comp=C._compressor(cfg), prefix=prefix,
                                **paged_kw)
    except OSError as e:          # corrupted stream or manifest
        print(f"checkpoint {d} unusable for paged serving ({e})")
        return None
    return store, {"step": manifest.get("step", s),
                   **manifest.get("extra", {})}


def serving_params_struct(model_cfg):
    """Serving holds params in bf16: re-reading + casting f32 masters every
    decode step doubles parameter HBM traffic for nothing (found via the
    §Perf HLO breakdown — see EXPERIMENTS.md)."""
    f32_struct = jax.eval_shape(
        lambda: T.init_params(jax.random.key(0), model_cfg))
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), f32_struct)


def make_decode_fn(model_cfg, plan: ShardingPlan, batch: int, cache_len: int):
    """Returns (jit_fn, token_struct, cache_struct, shardings)."""
    cache_struct = jax.eval_shape(
        lambda: T.init_cache(model_cfg, batch, cache_len))
    # mark a mid-stream position so the lowering is position-generic
    token_struct = jax.ShapeDtypeStruct((batch,), jnp.int32)

    batch_ok = plan.mesh is None or batch % int(np.prod(
        [plan.axis_size(a) for a in plan.batch_axes])) == 0
    plan = dataclasses.replace(plan, decode_wide=not batch_ok)

    def decode(params, token, cache):
        return T.serve_decode(params, model_cfg, token, cache, plan)
    cs = cache_shardings(cache_struct, plan, batch_sharded=batch_ok)
    ts = (NamedSharding(plan.mesh, P(plan.batch if batch_ok else None))
          if plan.mesh else None)
    return decode, token_struct, cache_struct, (ts, cs)


def make_prefill_fn(model_cfg, plan: ShardingPlan, batch: int, seq: int):
    """Returns (fn, ordered_arg_structs, ordered_arg_shardings) where the
    structs follow fn's positional order after params: (tokens[, frontend])."""
    text = seq
    structs: Dict[str, Any] = {}
    if model_cfg.frontend == "vision":
        text = seq - model_cfg.frontend_len
        structs["frontend"] = jax.ShapeDtypeStruct(
            (batch, model_cfg.frontend_len, model_cfg.d_model), jnp.float32)
    elif model_cfg.frontend == "audio":
        structs["frontend"] = jax.ShapeDtypeStruct(
            (batch, model_cfg.encoder.n_frames, model_cfg.d_model),
            jnp.float32)
    structs = {"tokens": jax.ShapeDtypeStruct((batch, text), jnp.int32),
               **structs}

    def prefill(params, tokens, frontend=None):
        return T.serve_prefill(params, model_cfg, tokens, plan,
                               frontend=frontend)

    args = [structs["tokens"]] + (
        [structs["frontend"]] if "frontend" in structs else [])
    shardings = tuple(
        (NamedSharding(plan.mesh,
                       P(plan.batch, *([None] * (len(v.shape) - 1))))
         if plan.mesh else None) for v in args)
    return prefill, args, shardings
