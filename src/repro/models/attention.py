"""Attention: blocked flash-style softmax attention (pure JAX), GQA/MQA,
sliding-window, cross-attention, MLA (DeepSeek multi-head latent attention),
and the sequence-sharded decode path for long contexts.

The blocked implementation is the memory workhorse: scores never materialize
beyond (Bq x Bk) tiles, so prefill_32k and train_4k lower without O(S^2)
buffers — the same online-softmax recurrence a Pallas/TPU flash kernel uses,
expressed with lax.scan so XLA fuses it. (GPU papers implement this as a CUDA
kernel; on TPU the scan body is already MXU matmuls + VPU rescaling, see
DESIGN.md §2.)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import Params, _init, apply_rope, init_dense, dense, rope_table

NEG_INF = -1e30


# --------------------------------------------------------------------------
# blocked attention core
# --------------------------------------------------------------------------
def _block_mask(q_pos, k_pos, *, causal: bool, window: int, is_global,
                kv_len) -> jnp.ndarray:
    """(Bq, Bk) bool mask. window>0 limits lookback; is_global (traced bool
    or None) switches window off per-layer; kv_len (traced or None) masks
    cache tail."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window:
        in_win = (q_pos[:, None] - k_pos[None, :]) < window
        if is_global is None:
            m &= in_win
        else:
            m &= jnp.logical_or(is_global, in_win)
    if kv_len is not None:
        m &= k_pos[None, :] < kv_len
    return m


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "softmax_scale",
                                             "vma"))
def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_offset: jnp.ndarray | int = 0,
                      causal: bool = True, window: int = 0,
                      is_global=None, kv_len=None,
                      block_q: int = 512, block_k: int = 512,
                      softmax_scale: float | None = None,
                      vma: tuple[str, ...] = ()) -> jnp.ndarray:
    """q: (B, Sq, H, D); k, v: (B, Skv, KH, Dk/Dv) with H % KH == 0 (GQA).

    Returns (B, Sq, H, Dv).  Online softmax over KV blocks, scanned over Q
    blocks; f32 accumulation.
    """
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    rep = h // kh

    if sq <= 4:
        # decode path: one dense pass, no scan -> GSPMD can shard the KV
        # sequence axis (flash-decoding emerges from the sharded softmax).
        return _dense_attention(q, k, v, q_offset=q_offset, causal=causal,
                                window=window, is_global=is_global,
                                kv_len=kv_len, scale=scale)

    bq = min(block_q, sq)
    nq = -(-sq // bq)
    pad_q = nq * bq - sq
    bk = min(block_k, skv)
    nk = -(-skv // bk)
    pad_k = nk * bk - skv

    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    # pad positions beyond the real kv range so masks kill them
    k_positions = jnp.arange(nk * bk)
    kv_len_eff = jnp.asarray(skv if kv_len is None else kv_len)

    qf = qf.reshape(b, nq, bq, h, d)
    kf = kf.reshape(b, nk, bk, kh, d)
    vf = vf.reshape(b, nk, bk, kh, dv)

    def q_block(carry, qi):
        qb, qpos = qi  # (B, bq, H, D), (bq,)

        def kv_block(state, ki):
            m_prev, l_prev, acc = state
            kb, vb, kpos = ki
            # grouped GQA: contract per kv-head group — NO jnp.repeat (a
            # repeat over a sharded head axis forces a full reshard)
            qg = qb.reshape(b, bq, kh, rep, d)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(qpos, kpos, causal=causal, window=window,
                               is_global=is_global, kv_len=kv_len_eff)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1)
            pv = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kh, rep, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, rep, bq), jnp.float32)
        a0 = jnp.zeros((b, kh, rep, bq, dv), jnp.float32)
        if vma:  # under shard_map: mark carries varying over manual axes
            m0, l0, a0 = (jax.lax.pvary(t, vma) for t in (m0, l0, a0))
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0),
             k_positions.reshape(nk, bk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (B, KH, rep, bq, Dv) -> (B, bq, H, Dv)
        return carry, jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, bq, h, dv)

    q_positions = (jnp.arange(nq * bq) + q_offset).reshape(nq, bq)
    _, blocks = jax.lax.scan(q_block, 0, (jnp.moveaxis(qf, 1, 0), q_positions))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, nq * bq, h, dv)
    return out[:, :sq].astype(q.dtype)


def _dense_attention(q, k, v, *, q_offset, causal, window, is_global,
                     kv_len, scale):
    """Decode path. Grouped GQA einsums (no repeat over the sharded head
    axis); softmax reductions over a sharded KV-sequence axis lower to the
    psum-combine of flash-decoding under GSPMD."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    rep = h // kh
    qg = q.reshape(b, sq, kh, rep, d)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = _block_mask(jnp.arange(sq) + q_offset, jnp.arange(skv),
                       causal=causal, window=window, is_global=is_global,
                       kv_len=kv_len)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------
# context-parallel attention (sequence sharded over the TP axis)
# --------------------------------------------------------------------------
def context_parallel_attention(q, k, v, *, mesh, dp, tp: str = "model",
                               causal=True, window=0, is_global=None,
                               block_q=512, block_k=512,
                               softmax_scale=None):
    """Shard the QUERY sequence over the tp axis; each rank runs blocked
    attention for its slab against the full K/V (replicated over tp — KV for
    GQA models is small).  Used when n_heads % tp_size != 0, where head-TP
    would otherwise leave attention unsharded and GSPMD emits an all-reduce
    per block pair (the starcoder2 2.4 TB/step pathology).  Causality is
    preserved by passing the slab's absolute q_offset.
    """
    p = mesh.shape[tp]
    sq = q.shape[1]
    pad = (-sq) % p
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    dpb = dp if q.shape[0] % _dp_size(mesh, dp) == 0 and q.shape[0] > 1 else None
    qspec = P(dpb, tp, None, None)
    kvspec = P(dpb, None, None, None)
    slab = (sq + pad) // p

    vma = tuple(mesh.axis_names)

    def body(qb, kb, vb):
        off = jax.lax.axis_index(tp) * slab
        return blocked_attention(qb, kb, vb, q_offset=off, causal=causal,
                                 window=window, is_global=is_global,
                                 block_q=min(block_q, slab), block_k=block_k,
                                 softmax_scale=softmax_scale, vma=vma)

    out = jax.shard_map(body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                        out_specs=qspec)(q, k, v)
    return out[:, :sq] if pad else out


def _dp_size(mesh, dp) -> int:
    n = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n *= mesh.shape[a]
    return n


# --------------------------------------------------------------------------
# GQA self-attention layer
# --------------------------------------------------------------------------
def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qk_norm: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": init_dense(ks[0], d_model, n_heads * head_dim),
        "wk": init_dense(ks[1], d_model, n_kv * head_dim),
        "wv": init_dense(ks[2], d_model, n_kv * head_dim),
        "wo": init_dense(ks[3], n_heads * head_dim, d_model),
    }
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), jnp.float32)}
    return p


def _head_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def attention(p: Params, x: jnp.ndarray, *, n_heads: int, n_kv: int,
              head_dim: int, positions: jnp.ndarray, rope_theta: float = 1e4,
              window: int = 0, is_global=None, qk_norm: bool = False,
              cache: dict | None = None, kv_len=None,
              block_q: int = 512, block_k: int = 512,
              cp_mesh=None, cp_dp=("data",),
              sharder=None) -> tuple[jnp.ndarray, dict | None]:
    """Self attention with optional KV cache.

    Train/prefill: positions (S,) (prefill passes kv_len=0 and a cache to
    fill; attention runs over the fresh block — correct since prefill starts
    the sequence).  Decode: cache holds {'k','v'} (B, Smax, KH, D), kv_len is
    the current length, x is the new token(s).
    cp_mesh: enable context-parallel attention (sequence sharded over the TP
    axis) — used when head-TP is impossible (n_heads % tp != 0).
    Returns (y, updated_cache).
    """
    b, s, _ = x.shape
    q = dense(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = dense(p["wk"], x).reshape(b, s, n_kv, head_dim)
    v = dense(p["wv"], x).reshape(b, s, n_kv, head_dim)
    if qk_norm:
        q = _head_norm(p["q_norm"], q)
        k = _head_norm(p["k_norm"], k)
    cos, sin = rope_table(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # §Perf iterations 5/6: at 32k prefill, GSPMD's own propagation (q-row
    # sharding inside each block, S^2/tp compute, no reshard) beats an
    # explicit head-TP boundary by 6.6x attention flops — so NO constraint
    # for long sequences. For short-seq training under the SP residual, the
    # measured auto-propagation produces a reshard storm (120k all-gathers,
    # 7.7 TB/step on gemma) — there the explicit seq->heads boundary wins.
    if sharder is not None and cache is None and cp_mesh is None and s <= 8192:
        q, k, v = sharder.heads(q), sharder.heads(k), sharder.heads(v)

    new_cache = None
    if cache is not None:
        start = kv_len if kv_len is not None else 0
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), start, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), start, axis=1)
        new_cache = {"k": ck, "v": cv}

    if cache is not None and s <= 4:  # decode: dense pass over the cache
        y = blocked_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                              q_offset=positions[0], causal=True,
                              window=window, is_global=is_global,
                              kv_len=(kv_len + s) if kv_len is not None else None,
                              block_q=block_q, block_k=block_k)
    elif cp_mesh is not None:  # train/prefill, context parallel
        y = context_parallel_attention(q, k, v, mesh=cp_mesh, dp=cp_dp,
                                       causal=True, window=window,
                                       is_global=is_global, block_q=block_q,
                                       block_k=block_k)
    else:  # train/prefill, head-TP
        y = blocked_attention(q, k, v, q_offset=0, causal=True, window=window,
                              is_global=is_global, block_q=block_q,
                              block_k=block_k)
    return dense(p["wo"], y.reshape(b, s, n_heads * head_dim)), new_cache


# --------------------------------------------------------------------------
# cross-attention (VLM decoder layers; KV from precomputed vision tokens)
# --------------------------------------------------------------------------
def init_cross_attention(key, d_model: int, n_heads: int, n_kv: int,
                         head_dim: int, d_kv_in: int | None = None) -> Params:
    ks = jax.random.split(key, 4)
    d_kv_in = d_kv_in or d_model
    return {
        "wq": init_dense(ks[0], d_model, n_heads * head_dim),
        "wk": init_dense(ks[1], d_kv_in, n_kv * head_dim),
        "wv": init_dense(ks[2], d_kv_in, n_kv * head_dim),
        "wo": init_dense(ks[3], n_heads * head_dim, d_model),
    }


def cross_attention(p: Params, x: jnp.ndarray, kv_src: jnp.ndarray, *,
                    n_heads: int, n_kv: int, head_dim: int,
                    block_q: int = 512, block_k: int = 512) -> jnp.ndarray:
    b, s, _ = x.shape
    skv = kv_src.shape[1]
    q = dense(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = dense(p["wk"], kv_src).reshape(b, skv, n_kv, head_dim)
    v = dense(p["wv"], kv_src).reshape(b, skv, n_kv, head_dim)
    y = blocked_attention(q, k, v, causal=False, block_q=block_q, block_k=block_k)
    return dense(p["wo"], y.reshape(b, s, n_heads * head_dim))


# --------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek V2)
# --------------------------------------------------------------------------
def init_mla(key, d_model: int, n_heads: int, *, kv_lora: int, nope_dim: int,
             rope_dim: int, v_dim: int) -> Params:
    ks = jax.random.split(key, 6)
    return {
        "wq": init_dense(ks[0], d_model, n_heads * (nope_dim + rope_dim)),
        "wdkv": init_dense(ks[1], d_model, kv_lora + rope_dim),
        "kv_norm": {"scale": jnp.ones((kv_lora,), jnp.float32)},
        "wuk": init_dense(ks[2], kv_lora, n_heads * nope_dim),
        "wuv": init_dense(ks[3], kv_lora, n_heads * v_dim),
        "wo": init_dense(ks[4], n_heads * v_dim, d_model),
    }


def mla_attention(p: Params, x: jnp.ndarray, *, n_heads: int, kv_lora: int,
                  nope_dim: int, rope_dim: int, v_dim: int,
                  positions: jnp.ndarray, rope_theta: float = 1e4,
                  cache: dict | None = None, kv_len=None,
                  block_q: int = 512, block_k: int = 512,
                  sharder=None) -> tuple[jnp.ndarray, dict | None]:
    """Train/prefill path: decompress K up-front, run blocked attention.
    Decode path (cache given): ABSORBED form — scores live in the kv_lora
    latent space, cache stores only (c_kv, k_rope): the paper-exact memory
    win (576 vs 2*H*D floats per position).
    """
    b, s, _ = x.shape
    hd = nope_dim + rope_dim
    q = dense(p["wq"], x).reshape(b, s, n_heads, hd)
    q_nope, q_rope = q[..., :nope_dim], q[..., nope_dim:]
    cos, sin = rope_table(positions, rope_dim, rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    dkv = dense(p["wdkv"], x)
    c_kv = _head_norm(p["kv_norm"], dkv[..., :kv_lora])
    k_rope = apply_rope(dkv[..., None, kv_lora:], cos, sin)  # (B,S,1,rope)

    if cache is None:
        wuk = p["wuk"]["w"].astype(x.dtype).reshape(kv_lora, n_heads, nope_dim)
        k_nope = jnp.einsum("bsc,chd->bshd", c_kv, wuk)
        v = jnp.einsum("bsc,chd->bshd", c_kv,
                       p["wuv"]["w"].astype(x.dtype).reshape(kv_lora, n_heads, v_dim))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, n_heads, rope_dim))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        del sharder  # see §Perf iteration 5 note in attention()
        y = blocked_attention(qq, k, v, causal=True, block_q=block_q,
                              block_k=block_k, softmax_scale=hd ** -0.5)
        new_cache = None
    else:
        # absorbed decode: q_abs = W_uk^T q_nope  in latent space
        start = kv_len if kv_len is not None else 0
        cc = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), start, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype), start, axis=1)
        new_cache = {"c_kv": cc, "k_rope": cr}
        wuk = p["wuk"]["w"].astype(x.dtype).reshape(kv_lora, n_heads, nope_dim)
        q_abs = jnp.einsum("bshd,chd->bshc", q_nope, wuk)     # (B,S,H,kv_lora)
        qq = jnp.concatenate([q_abs, q_rope], -1)             # (B,S,H,kv_lora+rope)
        kk = jnp.concatenate([cc, cr], -1)[:, :, None, :].astype(x.dtype)  # (B,Smax,1,c+r)
        y_lat = blocked_attention(qq, kk, kk[..., :kv_lora],
                                  q_offset=positions[0], causal=True,
                                  kv_len=(kv_len + s) if kv_len is not None else None,
                                  block_q=block_q, block_k=block_k,
                                  softmax_scale=hd ** -0.5)   # (B,S,H,kv_lora)
        wuv = p["wuv"]["w"].astype(x.dtype).reshape(kv_lora, n_heads, v_dim)
        y = jnp.einsum("bshc,chd->bshd", y_lat, wuv)
        return dense(p["wo"], y.reshape(b, s, n_heads * v_dim)), new_cache

    return dense(p["wo"], y.reshape(b, s, n_heads * v_dim)), new_cache
