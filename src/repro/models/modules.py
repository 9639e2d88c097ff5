"""Shared model building blocks (pure JAX, pytree params, no framework).

Design rules:
  * every `*_init` returns a nested dict of f32 arrays whose key paths match
    runtime.sharding.PARAM_RULES (that is how TP/EP placement is derived);
  * every `*_apply` is pure, takes a ShardingPlan (mesh=None => no-op
    constraints) and computes in bf16 with f32 accumulation where it
    matters (softmax, norms, loss);
  * attention uses a chunked two-level-scan flash implementation so a 32k
    context never materializes an (S, S) score matrix (required for the
    dry-run memory footprint at prefill_32k/train_4k).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..runtime.sharding import ShardingPlan

Dtype = jnp.dtype
COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return (scale * jax.random.normal(key, shape, jnp.float32))


def dense_init(key, in_dim, out_shape, scale=None):
    """Fan-in scaled normal; out_shape may be multi-dim (heads, head_dim)."""
    if scale is None:
        scale = in_dim ** -0.5
    return _normal(key, (in_dim,) + tuple(np.atleast_1d(out_shape)), scale)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(dim, layernorm: bool = False):
    p = {"scale": jnp.zeros(dim, jnp.float32)}       # gemma-style (1+scale)
    if layernorm:
        p["bias"] = jnp.zeros(dim, jnp.float32)
    return p


def norm_apply(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:                                   # LayerNorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * (1.0 + p["scale"]) + p["bias"]
    else:                                             # RMSNorm
        var = (xf ** 2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE, partial RoPE, M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, rotary_dim: Optional[int] = None):
    rd = rotary_dim or head_dim
    inv = 1.0 / (theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd))
    return jnp.asarray(inv, jnp.float32)              # (rd/2,)


def apply_rope(x, positions, inv_freqs, rotary_dim: Optional[int] = None):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    rd = (rotary_dim or x.shape[-1])
    ang = positions[..., :, None].astype(jnp.float32) * inv_freqs  # (...,S,rd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    cos = jnp.cos(ang)[..., :, None, :]
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., rd:]], -1)


def apply_mrope(x, positions3, inv_freqs, sections: Tuple[int, int, int]):
    """Qwen2-VL multimodal RoPE: the rd/2 frequency lanes are split into
    (t, h, w) sections, each driven by its own position stream.
    positions3: (3, ..., S)."""
    secs = np.cumsum((0,) + tuple(sections))
    ang_parts = []
    for i in range(3):
        f = inv_freqs[secs[i]:secs[i + 1]]
        ang_parts.append(positions3[i][..., :, None].astype(jnp.float32) * f)
    ang = jnp.concatenate(ang_parts, -1)             # (..., S, rd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    cos = jnp.cos(ang)[..., :, None, :]
    rd = 2 * int(secs[-1])
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., rd:]], -1)


# ---------------------------------------------------------------------------
# flash attention (pure JAX, chunked double scan)
# ---------------------------------------------------------------------------

NEG_INF = -2.0e38


def _chunk(x, size, axis):
    n = x.shape[axis] // size
    new_shape = x.shape[:axis] + (n, size) + x.shape[axis + 1:]
    return x.reshape(new_shape)


def _block_scores(qblk, kblk, cfg, qi, kj):
    """(B, H, bq, bk) f32 masked scores for one (q-chunk, kv-chunk) pair."""
    causal, window, q_offset, bq, bk, scale, Sk_real = cfg
    B = qblk.shape[0]
    K, D = kblk.shape[2], kblk.shape[3]
    H = qblk.shape[2]
    G = H // K
    q_pos = q_offset + qi * bq + jnp.arange(bq)
    k_pos = kj * bk + jnp.arange(bk)
    qg = qblk.reshape(B, bq, K, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kblk,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(B, H, bq, bk)
    mask = jnp.broadcast_to(k_pos[None, :] < Sk_real, (bq, bk))
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(mask[None, None], s, NEG_INF)


def _flash_fwd(cfg, q, k, v):
    """-> (out (B,Sq,H,Dv), lse (B,H,Sq))."""
    causal, window, q_offset, bq, bk, scale, Sk_real = cfg
    B, Sq, H, D = q.shape
    K, Dv = k.shape[2], v.shape[-1]
    G = H // K
    nq, nk = Sq // bq, k.shape[1] // bk
    qc = jnp.moveaxis(_chunk(q, bq, 1), 1, 0)
    kc = jnp.moveaxis(_chunk(k, bk, 1), 1, 0)
    vc = jnp.moveaxis(_chunk(v, bk, 1), 1, 0)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, kblk, vblk = kj_blk
            s = _block_scores(qblk, kblk, cfg, qi, kj)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pg = p.reshape(B, K, G, bq, bk)
            pvg = jnp.einsum("bkgqs,bskd->bkgqd", pg.astype(jnp.bfloat16),
                             vblk.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pvg.reshape(B, H, bq, Dv)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, (jnp.arange(nq), qc))
    out = jnp.moveaxis(outs, 0, 2).reshape(B, H, Sq, Dv)
    lse = jnp.moveaxis(lses, 0, 2).reshape(B, H, Sq)
    return out.transpose(0, 2, 1, 3), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg, q, k, v):
    return _flash_fwd(cfg, q, k, v)[0]


def _flash_fwd_rule(cfg, q, k, v):
    out, lse = _flash_fwd(cfg, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(cfg, res, do):
    """Flash backward: recompute scores blockwise — nothing S x S is ever
    stored (this is the reason flash_attention carries a custom_vjp: the
    naive scan backward stacks every (bq,bk) score block as a residual,
    which XLA materializes as the full score tensor; measured 474 GB/chip
    on zamba2-7b/train_4k — EXPERIMENTS.md §Perf)."""
    causal, window, q_offset, bq, bk, scale, Sk_real = cfg
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    nq, nk = Sq // bq, Sk // bk
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       out.astype(jnp.float32))            # (B,H,Sq)
    qc = jnp.moveaxis(_chunk(q, bq, 1), 1, 0)              # (nq,B,bq,H,D)
    doc = jnp.moveaxis(_chunk(do, bq, 1), 1, 0)
    dc = jnp.moveaxis(_chunk(delta.transpose(0, 2, 1), bq, 1), 1, 0)
    lc = jnp.moveaxis(_chunk(lse.transpose(0, 2, 1), bq, 1), 1, 0)
    kc = jnp.moveaxis(_chunk(k, bk, 1), 1, 0)              # (nk,B,bk,K,D)
    vc = jnp.moveaxis(_chunk(v, bk, 1), 1, 0)

    def kv_step(dq_acc, kj_blk):
        kj, kblk, vblk = kj_blk

        def q_step(carry, qi_blk):
            dk_a, dv_a = carry
            qi, qblk, doblk, dblk, lblk = qi_blk
            s = _block_scores(qblk, kblk, cfg, qi, kj)     # (B,H,bq,bk)
            p = jnp.exp(s - lblk.transpose(0, 2, 1)[..., None])
            pg = p.reshape(B, K, G, bq, bk).astype(jnp.bfloat16)
            dog = doblk.reshape(B, bq, K, G, Dv).astype(jnp.bfloat16)
            dv_a = dv_a + jnp.einsum("bkgqs,bqkgd->bskd", pg, dog,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", dog,
                            vblk.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
            ds = p.reshape(B, K, G, bq, bk) * (
                dp - dblk.transpose(0, 2, 1).reshape(
                    B, K, G, bq)[..., None])
            ds = (ds * scale).astype(jnp.bfloat16)
            dq_blk = jnp.einsum("bkgqs,bskd->bqkgd", ds,
                                kblk.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            dk_a = dk_a + jnp.einsum("bkgqs,bqkgd->bskd", ds,
                                     qblk.reshape(B, bq, K, G, D)
                                     .astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32)
            return (dk_a, dv_a), dq_blk.reshape(B, bq, H, D)

        zk = jnp.zeros((B, bk, K, D), jnp.float32)
        zv = jnp.zeros((B, bk, K, Dv), jnp.float32)
        (dk_j, dv_j), dq_blocks = jax.lax.scan(
            q_step, (zk, zv), (jnp.arange(nq), qc, doc, dc, lc))
        dq_new = jnp.moveaxis(dq_blocks, 0, 1).reshape(B, Sq, H, D)
        return dq_acc + dq_new, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0,
                                  (jnp.arange(nk), kc, vc))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, K, D)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, K, Dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                    q_offset: int = 0, bq: int = 512, bk: int = 1024,
                    scale: Optional[float] = None):
    """q: (B, Sq, H, D); k/v: (B, Sk, K, D) with H % K == 0 (GQA).

    Returns (B, Sq, H, D). Never materializes more than (B, H, bq, bk)
    scores — in EITHER direction: the custom_vjp backward recomputes score
    blocks instead of saving them. Masking is positional: query i attends
    keys j with j <= i + q_offset (causal), j > i + q_offset - window.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Dv = v.shape[-1]                         # may differ from D (MLA)
    scale = scale if scale is not None else D ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    # pad to chunk multiples (whisper's 1500 frames, VLM text tails);
    # padded keys are masked via Sk_real, padded queries sliced off
    Sq_real, Sk_real = Sq, Sk
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    cfg = (causal, window, q_offset, bq, bk, scale, Sk_real)
    out = _flash(cfg, q, k, v)
    return out[:, :Sq_real]


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rotary_frac: float = 1.0               # partial rotary (GLM: 0.5)
    window: Optional[int] = None           # sliding window (gemma3 local)
    qk_norm: bool = False                  # gemma3
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl
    causal: bool = True
    query_scale: Optional[float] = None    # override 1/sqrt(D)


def attn_init(key, cfg: AttnConfig):
    ks = jax.random.split(key, 6)
    d, H, K, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, (H, D)),
        "wk": dense_init(ks[1], d, (K, D)),
        "wv": dense_init(ks[2], d, (K, D)),
        "wo": _normal(ks[3], (H, D, d), (H * D) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(D)
        p["k_norm"] = norm_init(D)
    return {"attn": p}


def _rotary_dim(cfg: AttnConfig) -> int:
    rd = int(cfg.head_dim * cfg.rotary_frac)
    return rd - rd % 2


def _qkv(p, cfg, x, positions, plan: ShardingPlan):
    ap = p["attn"]
    dt = x.dtype
    q = jnp.einsum("btd,dhk->bthk", x, ap["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", x, ap["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", x, ap["wv"].astype(dt))
    q = plan.act_bthd(q)
    if cfg.qk_norm:
        q = norm_apply(ap["q_norm"], q)
        k = norm_apply(ap["k_norm"], k)
    inv = rope_freqs(cfg.head_dim, cfg.rope_theta, _rotary_dim(cfg))
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, inv, cfg.mrope_sections)
        k = apply_mrope(k, positions, inv, cfg.mrope_sections)
    elif cfg.rotary_frac > 0:
        q = apply_rope(q, positions, inv, _rotary_dim(cfg))
        k = apply_rope(k, positions, inv, _rotary_dim(cfg))
    return q, k, v


def attn_apply(p, cfg: AttnConfig, x, positions, plan: ShardingPlan,
               q_offset: int = 0):
    """Training / prefill path. x: (B, S, d). Returns (out, (k, v))."""
    q, k, v = _qkv(p, cfg, x, positions, plan)
    out = flash_attention(q, k, v, causal=cfg.causal, window=cfg.window,
                          q_offset=q_offset, scale=cfg.query_scale)
    out = plan.act_bthd(out)
    y = jnp.einsum("bthk,hkd->btd", out, p["attn"]["wo"].astype(x.dtype))
    return plan.act_btd(y), (k, v)


def cross_attn_apply(p, cfg: AttnConfig, x, memory, plan: ShardingPlan):
    """Encoder-decoder cross attention (whisper). No RoPE, non-causal."""
    ap = p["attn"]
    dt = x.dtype
    q = jnp.einsum("btd,dhk->bthk", x, ap["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", memory, ap["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", memory, ap["wv"].astype(dt))
    out = flash_attention(q, k, v, causal=False, scale=cfg.query_scale)
    y = jnp.einsum("bthk,hkd->btd", out, ap["wo"].astype(dt))
    return plan.act_btd(y)


def masked_cache_write(cache_seq, new, slot):
    """Write (B,1,...) `new` at position `slot` (B,) along axis 1 via an
    iota-compare select. Elementwise => each shard of a SEQUENCE-SHARDED
    cache updates locally; a dynamic_update_slice here would make the SPMD
    partitioner re-gather the whole cache to move one token (measured:
    ~200 MB/chip/step at 500k context — see EXPERIMENTS.md §Perf)."""
    L = cache_seq.shape[1]
    idx = jnp.arange(L)
    hit = (idx[None, :] == slot[:, None])            # (B, L)
    hit = hit.reshape(hit.shape + (1,) * (cache_seq.ndim - 2))
    return jnp.where(hit, new.astype(cache_seq.dtype), cache_seq)


def attn_decode(p, cfg: AttnConfig, x, pos, cache, plan: ShardingPlan):
    """Single-token decode. x: (B, 1, d); cache: dict(k,v): (B, S, K, D).

    The KV cache sequence dim is sharded over the model axis (context
    parallelism — required to fit 32k-500k contexts); the merge across
    sequence shards is a log-sum-exp partial-softmax reduction that XLA
    lowers from the einsum + max/sum reductions under the sharding
    constraints below.
    """
    q, k_new, v_new = _qkv(p, cfg, x, pos[..., None] if pos.ndim == 1 else pos,
                           plan)
    # write the new token into the cache at `pos` (locally per shard)
    k_cache = masked_cache_write(cache["k"], k_new, pos)
    v_cache = masked_cache_write(cache["v"], v_new, pos)
    cb, cseq = plan.cache_kv_spec()
    k_cache = plan.cs(k_cache, cb, cseq, None, None)
    v_cache = plan.cs(v_cache, cb, cseq, None, None)

    B, S, K, D = k_cache.shape
    H = cfg.n_heads
    G = H // K
    scale = cfg.query_scale if cfg.query_scale is not None else D ** -0.5
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(S)
    mask = k_pos[None, :] <= pos[:, None]
    if cfg.window is not None:
        mask &= k_pos[None, :] > (pos[:, None] - cfg.window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w.astype(q.dtype),
                     v_cache.astype(q.dtype))
    out = out.reshape(B, 1, H, D)
    y = jnp.einsum("bthk,hkd->btd", out, p["attn"]["wo"].astype(x.dtype))
    return plan.act_btd(y), {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora: int = 1536
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    rope_theta: float = 10000.0


def mla_init(key, cfg: MLAConfig):
    ks = jax.random.split(key, 8)
    H = cfg.n_heads
    p = {
        "wq_a": dense_init(ks[0], cfg.d_model, (cfg.q_lora,)),
        "wq_b": dense_init(ks[1], cfg.q_lora, (H, cfg.qk_nope + cfg.qk_rope)),
        "wkv_a": dense_init(ks[2], cfg.d_model, (cfg.kv_lora + cfg.qk_rope,)),
        "wkv_b": dense_init(ks[3], cfg.kv_lora,
                            (H, cfg.qk_nope + cfg.v_head)),
        "wo": _normal(ks[4], (H, cfg.v_head, cfg.d_model),
                      (H * cfg.v_head) ** -0.5),
        "q_a_norm": norm_init(cfg.q_lora),
        "kv_a_norm": norm_init(cfg.kv_lora),
    }
    return {"mla": p}


def mla_apply(p, cfg: MLAConfig, x, positions, plan: ShardingPlan,
              q_offset: int = 0):
    """Training/prefill MLA. Returns (out, c_kv cache tuple)."""
    mp = p["mla"]
    dt = x.dtype
    B, S, _ = x.shape
    H = cfg.n_heads
    cq = norm_apply(mp["q_a_norm"], jnp.einsum("btd,dq->btq", x,
                                               mp["wq_a"].astype(dt)))
    q = jnp.einsum("btq,qhk->bthk", cq, mp["wq_b"].astype(dt))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope], axis=-1)
    kv_a = jnp.einsum("btd,dc->btc", x, mp["wkv_a"].astype(dt))
    c_kv, k_rope = jnp.split(kv_a, [cfg.kv_lora], axis=-1)
    c_kv = norm_apply(mp["kv_a_norm"], c_kv)
    kv = jnp.einsum("btc,chk->bthk", c_kv, mp["wkv_b"].astype(dt))
    k_nope, v = jnp.split(kv, [cfg.qk_nope], axis=-1)
    inv = rope_freqs(cfg.qk_rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, positions, inv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, inv)  # (B,S,1,r)
    k_rope_b = jnp.broadcast_to(k_rope, (B, S, H, cfg.qk_rope))
    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, k_rope_b], -1)
    qf = plan.act_bthd(qf)
    kf = plan.act_bthd(kf)
    scale = (cfg.qk_nope + cfg.qk_rope) ** -0.5
    out = flash_attention(qf, kf, v, causal=True, q_offset=q_offset,
                          scale=scale)
    out = plan.act_bthd(out)
    y = jnp.einsum("bthk,hkd->btd", out, mp["wo"].astype(dt))
    return plan.act_btd(y), (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, cfg: MLAConfig, x, pos, cache, plan: ShardingPlan):
    """Decode with the COMPRESSED cache (c_kv + k_rope) — the MLA memory
    win: per-token cache is kv_lora + qk_rope = 576 floats vs H*(K+V)."""
    mp = p["mla"]
    dt = x.dtype
    B = x.shape[0]
    H = cfg.n_heads
    cq = norm_apply(mp["q_a_norm"], jnp.einsum("btd,dq->btq", x,
                                               mp["wq_a"].astype(dt)))
    q = jnp.einsum("btq,qhk->bthk", cq, mp["wq_b"].astype(dt))[:, 0]
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope], axis=-1)    # (B,H,*)
    kv_a = jnp.einsum("btd,dc->btc", x, mp["wkv_a"].astype(dt))[:, 0]
    c_new, kr_new = jnp.split(kv_a, [cfg.kv_lora], axis=-1)
    c_new = norm_apply(mp["kv_a_norm"], c_new)
    inv = rope_freqs(cfg.qk_rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope[:, None], pos[..., None], inv)[:, 0]
    kr_new = apply_rope(kr_new[:, None, None, :], pos[:, None], inv)[:, 0, 0]
    ck = masked_cache_write(cache["c_kv"], c_new[:, None], pos)
    kr = masked_cache_write(cache["k_rope"], kr_new[:, None], pos)
    cb, cseq = plan.cache_kv_spec()
    ck = plan.cs(ck, cb, cseq, None)
    kr = plan.cs(kr, cb, cseq, None)
    # absorbed attention: score = q_nope . (W_kvb_k c) + q_rope . k_rope
    w_kv = mp["wkv_b"].astype(dt)                      # (c, H, nope+v)
    w_k = w_kv[..., :cfg.qk_nope]                      # (c, H, nope)
    w_v = w_kv[..., cfg.qk_nope:]                      # (c, H, v)
    q_abs = jnp.einsum("bhk,chk->bhc", q_nope, w_k)    # (B, H, c)
    s = (jnp.einsum("bhc,bsc->bhs", q_abs, ck.astype(dt),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_rope, kr.astype(dt),
                      preferred_element_type=jnp.float32))
    s = s * ((cfg.qk_nope + cfg.qk_rope) ** -0.5)
    S = ck.shape[1]
    mask = jnp.arange(S)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsc->bhc", w.astype(dt), ck.astype(dt))
    out = jnp.einsum("bhc,chv->bhv", ctx, w_v)         # (B, H, v_head)
    y = jnp.einsum("bhv,hvd->bd", out, mp["wo"].astype(dt))[:, None]
    return plan.act_btd(y), {"c_kv": ck, "k_rope": kr}


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / ReLU^2)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model, d_ff, gated: bool = True):
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], d_model, (d_ff,)),
         "wo": _normal(ks[1], (d_ff, d_model), d_ff ** -0.5)}
    if gated:
        p["wg"] = dense_init(ks[2], d_model, (d_ff,))
    return {"mlp": p}


def _act(name, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def mlp_apply(p, x, plan: ShardingPlan, act: str = "silu"):
    mp = p["mlp"]
    dt = x.dtype
    h = jnp.einsum("btd,df->btf", x, mp["wi"].astype(dt))
    if "wg" in mp:
        g = jnp.einsum("btd,df->btf", x, mp["wg"].astype(dt))
        h = _act(act, g) * h
    else:
        h = _act(act, h)
    h = plan.act_btf(h)
    y = jnp.einsum("btf,fd->btd", h, mp["wo"].astype(dt))
    return plan.act_btd(y)


# ---------------------------------------------------------------------------
# MoE (token-choice top-k, static capacity, expert-parallel over model axis)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0            # shared-expert count (DeepSeek)
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    act: str = "silu"


def moe_init(key, cfg: MoEConfig):
    ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": dense_init(ks[0], d, (E,), scale=d ** -0.5),
        "wi": _normal(ks[1], (E, d, f), d ** -0.5),
        "wg": _normal(ks[2], (E, d, f), d ** -0.5),
        "wo": _normal(ks[3], (E, f, d), f ** -0.5),
    }
    out = {"moe": p}
    if cfg.n_shared:
        out["shared"] = mlp_init(ks[4], d, cfg.shared_d_ff or f * cfg.n_shared)
    return out


def _moe_capacity(tokens: int, cfg: MoEConfig, n_local_experts: int) -> int:
    cap = int(np.ceil(tokens * cfg.top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(8, -(-cap // 8) * 8)


def moe_local_math(x2d, mp, cfg: MoEConfig, first_expert, n_local, capacity):
    """Token-choice top-k with static capacity on ONE expert shard.

    x2d: (T, d) tokens visible to this shard (replicated across EP ranks).
    Computes only experts [first_expert, first_expert + n_local); the
    caller psums across EP ranks. Scatter/gather based — no (T, E, C)
    one-hot dispatch tensor is ever built (that is what makes 160-expert
    DeepSeek trainable at 65k tokens/device).
    """
    T, d = x2d.shape
    dt = x2d.dtype
    logits = jnp.einsum("td,de->te", x2d, mp["router"].astype(dt))
    gates = jax.nn.softmax(logits.astype(jnp.float32), -1)
    top_w, top_i = jax.lax.top_k(gates, cfg.top_k)            # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    flat_e = top_i.reshape(-1)                                # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), cfg.top_k)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(se, length=cfg.n_experts)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * cfg.top_k) - starts[se]
    e_loc = se - first_expert
    valid = (e_loc >= 0) & (e_loc < n_local) & (pos < capacity)
    safe_e = jnp.where(valid, e_loc, 0)
    safe_p = jnp.where(valid, pos, capacity)                  # dump slot
    buf = jnp.zeros((n_local, capacity + 1, d), dt)
    buf = buf.at[safe_e, safe_p].set(jnp.where(valid[:, None],
                                               x2d[st], 0).astype(dt))
    buf = buf[:, :capacity]
    # expert FFN (gated)
    h = jnp.einsum("ecd,edf->ecf", buf, mp["wi"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", buf, mp["wg"].astype(dt))
    h = _act(cfg.act, g) * h
    y_buf = jnp.einsum("ecf,efd->ecd", h, mp["wo"].astype(dt))
    y_buf = jnp.concatenate([y_buf, jnp.zeros((n_local, 1, d), dt)], 1)
    y_pairs = y_buf[safe_e, safe_p] * jnp.where(valid, sw, 0.0)[:, None]
    # combine back to tokens (scatter-add over token ids)
    y = jnp.zeros((T, d), jnp.float32).at[st].add(y_pairs.astype(jnp.float32))
    # router aux (load balance) on this shard's view
    me = gates.mean(0)
    ce = counts.astype(jnp.float32) / jnp.maximum(counts.sum(), 1)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return y.astype(dt), aux


def moe_apply(p, cfg: MoEConfig, x, plan: ShardingPlan):
    """x: (B, S, d) -> (y, aux_loss). EP via shard_map over the model axis
    when a mesh is present; plain local math otherwise."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    mp = p["moe"]

    if plan.mesh is None or plan.model_size == 1:
        cap = _moe_capacity(B * S, cfg, cfg.n_experts)
        y, aux = moe_local_math(x2d, mp, cfg, 0, cfg.n_experts, cap)
    else:
        ms = plan.model_size
        assert cfg.n_experts % ms == 0, "experts must divide model axis"
        n_local = cfg.n_experts // ms
        cap = _moe_capacity(B * S // int(np.prod([
            plan.axis_size(a) for a in plan.batch_axes])), cfg, n_local)

        def shard_fn(x_loc, router, wi, wg, wo):
            ax = jax.lax.axis_index(plan.model_axis)
            mp_loc = {"router": router, "wi": wi, "wg": wg, "wo": wo}
            y_loc, aux = moe_local_math(x_loc, mp_loc, cfg, ax * n_local,
                                        n_local, cap)
            y_loc = jax.lax.psum(y_loc, plan.model_axis)
            # aux is model-invarying (inputs replicated over model); average
            # over the batch axes it varies on => fully replicated out P()
            aux = jax.lax.pmean(aux, tuple(plan.batch_axes))
            return y_loc, aux

        # manual over (batch axes, model); any remaining mesh axes (e.g. the
        # outer 'pod' axis when nested inside the compressed-reduction
        # shard_map) stay auto — this is what lets EP compose with the
        # paper's cross-pod compression wrapper. When already inside a
        # shard_map, the context mesh carries manual axis types and MUST be
        # the one passed down.
        manual = set(plan.batch_axes) | {plan.model_axis}
        mesh_arg = plan.mesh
        ctx = jax.sharding.get_abstract_mesh()
        if ctx is not None and not ctx.empty and any(
                t == jax.sharding.AxisType.Manual
                for t in getattr(ctx, "axis_types", ())):
            mesh_arg = None     # nested: bind only our axis_names on the
            # ambient (partially-manual) mesh
        y, aux = jax.shard_map(
            shard_fn, mesh=mesh_arg,
            in_specs=(P(plan.batch, None), P(None, None),
                      P(plan.model_axis, None, None),
                      P(plan.model_axis, None, None),
                      P(plan.model_axis, None, None)),
            out_specs=(P(plan.batch, None), P()),
            axis_names=manual,
            check_vma=False,
        )(x2d, mp["router"], mp["wi"], mp["wg"], mp["wo"])
        aux = jnp.mean(aux)

    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + mlp_apply({"mlp": p["shared"]["mlp"]}, x, plan, act=cfg.act)
    return plan.act_btd(y), aux


# ---------------------------------------------------------------------------
# embedding + chunked softmax cross-entropy (vocab-sharded, seq-chunked)
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int):
    # d^-0.5 keeps tied-head logits O(1) at init (loss starts near ln V)
    return {"embed": {"table": _normal(key, (vocab, d_model),
                                       d_model ** -0.5)}}


def embed_apply(p, tokens, plan: ShardingPlan, scale: Optional[float] = None):
    table = p["embed"]["table"].astype(COMPUTE_DTYPE)
    x = jnp.take(table, tokens, axis=0)
    if scale is not None:
        x = x * jnp.asarray(scale, COMPUTE_DTYPE)
    return plan.act_btd(x)


def unembed_logits(p, h, plan: ShardingPlan, softcap: Optional[float] = None):
    table = p["embed"]["table"].astype(h.dtype)
    logits = jnp.einsum("btd,vd->btv", h, table)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return plan.logits_btv(logits)


def chunked_xent(p, h, labels, plan: ShardingPlan,
                 softcap: Optional[float] = None, chunk: int = 512):
    """Cross-entropy without materializing (B, S, V) at once: scan over
    sequence chunks; logits stay vocab-sharded over the model axis."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    while S % chunk:                 # largest divisor of S at most `chunk`
        chunk -= 1
    n = S // chunk
    hc = jnp.moveaxis(h.reshape(B, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    def step(carry, hl):
        hh, ll = hl
        logits = unembed_logits(p, hh, plan, softcap).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, ll[..., None], -1)[..., 0]
        return carry + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(step, jnp.float32(0.0), (hc, lc))
    return total / (B * S)
