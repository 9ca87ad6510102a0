"""GQA attention with RoPE variants, qk-norm, KV cache, and a
memory-efficient pure-jnp flash path.

Why a jnp flash path exists alongside the Pallas kernel: the multi-pod
dry-run lowers for the CPU host platform where Pallas TPU kernels cannot
lower, and GSPMD partitions plain-jnp code best.  ``chunked_attention`` is
an online-softmax double loop (lax.scan over q-blocks and kv-blocks) whose
peak live buffer is [B, H, bq, bk] — the jnp twin of the Pallas kernel's
VMEM tiling, and the only way a 32k-token prefill fits at all.

RoPE variants (per assigned architectures):
  * 'rope'    — standard 1d rotary (Mistral/StarCoder2/Qwen3/Jamba/Granite)
  * 'rope2d'  — ChatGLM-style: rotary over the first half of head dim on
                stream-0 positions, second half on stream-1 positions
  * 'mrope'   — Qwen2-VL M-RoPE: head dim split into 3 sections
                (temporal/height/width), one position stream each
  * 'none'    — HuBERT (encoder uses learned/conv positions upstream)
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import init_linear, rms_norm

__all__ = [
    "init_attention",
    "attention_block",
    "decode_attention_block",
    "chunked_attention",
    "rope_frequencies",
    "apply_rope",
]

Params = Dict[str, jnp.ndarray]


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #


def rope_frequencies(d: int, theta: float = 10_000.0) -> jnp.ndarray:
    """Inverse frequencies for a rotary span of ``d`` dims (d even)."""
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def _rotate(x: jnp.ndarray, pos: jnp.ndarray, inv_freq: jnp.ndarray) -> jnp.ndarray:
    """x [..., S, d_span] rotated by pos [..., S] (broadcastable)."""
    ang = pos[..., None].astype(jnp.float32) * inv_freq  # [..., S, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(
    x: jnp.ndarray,  # [B, H, S, D]
    positions: jnp.ndarray,  # [B, S] ('rope') or [B, n_streams, S]
    variant: str = "rope",
    theta: float = 10_000.0,
) -> jnp.ndarray:
    B, H, S, D = x.shape
    if variant == "none":
        return x
    if variant == "rope":
        pos = positions if positions.ndim == 2 else positions[:, 0]
        inv = rope_frequencies(D, theta)
        return _rotate(x, pos[:, None, :], inv)
    if variant == "rope2d":
        # ChatGLM: two independent rotary halves on two position streams
        assert positions.ndim == 3 and positions.shape[1] >= 2
        half = D // 2
        inv = rope_frequencies(half, theta)
        a = _rotate(x[..., :half], positions[:, 0][:, None, :], inv)
        b = _rotate(x[..., half:], positions[:, 1][:, None, :], inv)
        return jnp.concatenate([a, b], axis=-1)
    if variant == "mrope":
        # Qwen2-VL: 3 sections (t, h, w); section sizes 2:1:1 of the head dim
        assert positions.ndim == 3 and positions.shape[1] >= 3
        s_t = D // 2
        s_h = D // 4
        s_w = D - s_t - s_h
        parts = []
        off = 0
        for span, stream in ((s_t, 0), (s_h, 1), (s_w, 2)):
            inv = rope_frequencies(span, theta)
            parts.append(
                _rotate(x[..., off : off + span], positions[:, stream][:, None, :], inv)
            )
            off += span
        return jnp.concatenate(parts, axis=-1)
    raise ValueError(f"unknown rope variant {variant!r}")


# --------------------------------------------------------------------------- #
# Memory-efficient attention (pure jnp, GSPMD-friendly)
# --------------------------------------------------------------------------- #


def chunked_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, Hk, Sk, D]
    v: jnp.ndarray,  # [B, Hk, Sk, D]
    causal: bool = True,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    window: Optional[int] = None,  # sliding-window attention span
) -> jnp.ndarray:
    """Online-softmax attention, peak live buffer [B, H, bq, bk]."""
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    g = H // Hk
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # pad S to block multiples (masked out below)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    nq, nk = qp.shape[2] // block_q, kp.shape[2] // block_k

    # fold GQA: [B, Hk, g, S, D]
    qg = qp.reshape(B, Hk, g, qp.shape[2], D)
    kb = kp.reshape(B, Hk, nk, block_k, D)
    vb = vp.reshape(B, Hk, nk, block_k, D)

    def q_block(qi, qtile):  # qtile [B, Hk, g, bq, D]
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, ktile, vtile = inp  # [B, Hk, bk, D]
            k_pos = ki * block_k + jnp.arange(block_k)
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk",
                qtile.astype(jnp.float32),
                ktile.astype(jnp.float32),
            ) * scale
            mask = k_pos[None, :] < Sk  # padded keys
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vtile.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hk, g, block_q), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hk, g, block_q), jnp.float32)
        a0 = jnp.zeros((B, Hk, g, block_q, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0)),
        )
        denom = jnp.where(l > 0, l, 1.0)
        return (acc / denom[..., None]).astype(q.dtype)

    if nq == 1:
        out = q_block(0, qg)
    else:
        qtiles = jnp.moveaxis(
            qg.reshape(B, Hk, g, nq, block_q, D), 3, 0
        )  # [nq, B, Hk, g, bq, D]
        out = jax.lax.map(lambda i_t: q_block(i_t[0], i_t[1]), (jnp.arange(nq), qtiles))
        out = jnp.moveaxis(out, 0, 3).reshape(B, Hk, g, nq * block_q, D)
    out = out.reshape(B, H, -1, D)
    return out[:, :, :Sq]


# --------------------------------------------------------------------------- #
# Attention block (projections + rope + cache)
# --------------------------------------------------------------------------- #


def init_attention(
    key, d_model: int, n_heads: int, n_kv_heads: int, d_head: int, qk_norm: bool = False
) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": init_linear(k1, d_model, n_heads * d_head),
        "wk": init_linear(k2, d_model, n_kv_heads * d_head),
        "wv": init_linear(k3, d_model, n_kv_heads * d_head),
        "wo": init_linear(k4, n_heads * d_head, d_model, scale=(n_heads * d_head) ** -0.5),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((d_head,), jnp.float32)
        p["k_norm"] = jnp.ones((d_head,), jnp.float32)
    return p


def _project_qkv(p: Params, x: jnp.ndarray, n_heads, n_kv_heads, d_head, positions, rope_variant, qk_norm, theta, q_offset_positions=None):
    B, S, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, n_heads, d_head).transpose(0, 2, 1, 3)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, n_kv_heads, d_head).transpose(0, 2, 1, 3)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, n_kv_heads, d_head).transpose(0, 2, 1, 3)
    if qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, rope_variant, theta)
    k = apply_rope(k, positions, rope_variant, theta)
    return q, k, v


def attention_block(
    p: Params,
    x: jnp.ndarray,  # [B, S, d_model]
    positions: jnp.ndarray,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    causal: bool = True,
    rope_variant: str = "rope",
    qk_norm: bool = False,
    theta: float = 10_000.0,
    window: Optional[int] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    scale: Optional[float] = None,  # None => d_head ** -0.5
) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, d_head, positions, rope_variant, qk_norm, theta)
    o = chunked_attention(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k, window=window
    )
    o = o.transpose(0, 2, 1, 3).reshape(B, S, n_heads * d_head)
    return o @ p["wo"].astype(x.dtype)


def decode_attention_block(
    p: Params,
    x: jnp.ndarray,  # [B, 1, d_model]
    positions: jnp.ndarray,  # [B, 1] (or [B, streams, 1])
    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],  # ([B, Hk, Smax, D], ...)
    cache_len,  # scalar int32: current cache fill
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    rope_variant: str = "rope",
    qk_norm: bool = False,
    theta: float = 10_000.0,
    window: Optional[int] = None,
    scale: Optional[float] = None,  # None => d_head ** -0.5
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Single-token decode with KV-cache update; returns (out, new_cache)."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, d_head, positions, rope_variant, qk_norm, theta)
    ck, cv = kv_cache
    Smax = ck.shape[2]
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, cache_len, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, cache_len, 0))
    g = n_heads // n_kv_heads
    qg = q.reshape(B, n_kv_heads, g, 1, d_head).astype(jnp.float32)
    if scale is None:
        scale = d_head ** -0.5
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, ck.astype(jnp.float32)) * scale
    kpos = jnp.arange(Smax)
    mask = kpos[None, :] <= cache_len
    if window is not None:
        mask = mask & (kpos[None, :] > cache_len - window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", w, cv.astype(jnp.float32))
    o = o.reshape(B, n_heads, 1, d_head).transpose(0, 2, 1, 3).reshape(B, 1, n_heads * d_head)
    return (o.astype(x.dtype) @ p["wo"].astype(x.dtype)), (ck, cv)
