"""Mamba2 block (SSD mixer) — attention-free sequence mixing.

Structure (Dao & Gu 2024, one B/C group):

  in_proj -> [z (H·P), x (H·P), B (N), C (N), dt (H)]
  depthwise causal conv1d (kernel 4, with bias) over x‖B‖C, then SiLU
  dt = softplus(dt + dt_bias);  SSD scan (kernels/ssd_scan.py, or the
  chunked jnp ref under GSPMD);  y + D·x
  gate: y ⊙ silu(z); RMSNorm; out_proj

Decode keeps two caches per layer: the conv tail [B, K-1, H·P + 2N] (the
pre-conv x‖B‖C stream) and the SSM state [B, H, N, P]; a decode step is
O(1) in sequence length, which is why the ``long_500k`` shape runs on this
family.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .layers import init_linear, rms_norm

__all__ = [
    "init_mamba2",
    "mamba2_block",
    "mamba2_decode",
    "mamba2_prefill",
    "init_mamba2_cache",
]

Params = Dict[str, jnp.ndarray]

CONV_K = 4


def init_mamba2(key, d_model: int, n_heads: int, d_head: int, d_state: int) -> Params:
    di = n_heads * d_head  # inner width
    conv_dim = di + 2 * d_state  # x‖B‖C
    ks = jax.random.split(key, 6)
    return {
        "in_proj": init_linear(ks[0], d_model, 2 * di + 2 * d_state + n_heads),
        "conv_w": jax.random.truncated_normal(ks[1], -3, 3, (CONV_K, conv_dim), jnp.float32) * 0.3,
        "conv_b": jax.random.truncated_normal(ks[3], -3, 3, (conv_dim,), jnp.float32) * 0.1,
        "A_log": jnp.log(jnp.linspace(1.0, 8.0, n_heads).astype(jnp.float32)),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),  # skip connection
        "norm": jnp.ones((di,), jnp.float32),
        "out_proj": init_linear(ks[2], di, d_model, scale=di ** -0.5),
    }


def _pad_seq(chunk: int, *arrays):
    """Pad the seq axis (axis 1) to a chunk multiple.  Zero-padding is exact
    for the SSD recurrence: padded steps have dt=0 (decay 1, zero input), so
    the state is unchanged and padded outputs are sliced away."""
    S = arrays[0].shape[1]
    pad = (-S) % chunk
    if pad == 0:
        return S, arrays
    out = tuple(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in arrays
    )
    return S, out


def _split_proj(p, u, n_heads, d_head, d_state):
    """in_proj output -> (z, x‖B‖C before the conv, dt)."""
    di = n_heads * d_head
    z = u[..., :di]
    xbc = u[..., di : 2 * di + 2 * d_state]
    dt = jax.nn.softplus(
        u[..., 2 * di + 2 * d_state :].astype(jnp.float32) + p["dt_bias"]
    )
    return z, xbc, dt


def _split_xbc(xbc, di: int, d_state: int):
    return xbc[..., :di], xbc[..., di : di + d_state], xbc[..., di + d_state :]


def _causal_conv(p, xbc):
    """Depthwise causal conv (kernel CONV_K, with bias) over the sequence
    axis of [B, S, conv_dim], then SiLU."""
    S = xbc.shape[1]
    xp = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    conv = sum(
        xp[:, i : i + S, :] * p["conv_w"][i].astype(xbc.dtype) for i in range(CONV_K)
    )
    return jax.nn.silu(conv + p["conv_b"].astype(xbc.dtype))


def mamba2_block(
    p: Params,
    h: jnp.ndarray,  # [B, S, d_model]
    n_heads: int,
    d_head: int,
    d_state: int,
    chunk: int = 128,
    eps: float = 1e-6,
) -> jnp.ndarray:
    B, S, _ = h.shape
    di = n_heads * d_head
    u = h @ p["in_proj"].astype(h.dtype)
    z, xbc, dt = _split_proj(p, u, n_heads, d_head, d_state)
    x, Bm, Cm = _split_xbc(_causal_conv(p, xbc), di, d_state)

    A = -jnp.exp(p["A_log"])  # [H] negative decay rates
    xh = x.reshape(B, S, n_heads, d_head)
    _, (xh_p, dt_p, B_p, C_p) = _pad_seq(
        chunk, xh, dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    )
    y = ops.ssd(xh_p, dt_p, A, B_p, C_p, chunk=chunk)[:, :S]
    y = y + xh * p["D"][None, None, :, None].astype(y.dtype)  # skip
    y = y.reshape(B, S, di)

    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], eps)
    return y @ p["out_proj"].astype(h.dtype)


def _final_state(xh, dt, A, Bm, chunk: int = 128):
    """SSM state after the full sequence (for prefill -> decode handoff).

    h_final = Σ_s dt_s·exp(Σ_{u>s} a_u)·B_s ⊗ x_s, computed chunk-blocked:
    per-chunk partial states folded left-to-right with chunk decays.
    """
    B, S0, H, P = xh.shape
    chunk = min(chunk, S0)
    _, (xh, dt, Bm) = _pad_seq(chunk, xh, dt, Bm)
    S = xh.shape[1]
    N = Bm.shape[-1]
    C = S // chunk
    f32 = jnp.float32
    x_ = xh.astype(f32).reshape(B, C, chunk, H, P)
    dt_ = dt.astype(f32).reshape(B, C, chunk, H)
    B_ = Bm.astype(f32).reshape(B, C, chunk, N)
    a = A.astype(f32)[None, None, None, :] * dt_
    acum = jnp.cumsum(a, axis=2)
    decay_to_end = jnp.exp(acum[:, :, -1:, :] - acum)
    S_c = jnp.einsum("bcsn,bcsh,bcshp->bchnp", B_, dt_ * decay_to_end, x_)
    chunk_decay = jnp.exp(acum[:, :, -1, :])  # [B, C, H]

    def fold(h, inp):
        s_c, dec = inp
        return dec[..., None, None] * h + s_c, None

    h0 = jnp.zeros((B, H, N, P), f32)
    h, _ = jax.lax.scan(
        fold, h0, (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    return h  # [B, H, N, P]


def mamba2_prefill(
    p: Params,
    h: jnp.ndarray,  # [B, S, d_model]
    n_heads: int,
    d_head: int,
    d_state: int,
    chunk: int = 128,
    eps: float = 1e-6,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence forward that also returns the decode cache."""
    B, S, _ = h.shape
    di = n_heads * d_head
    u = h @ p["in_proj"].astype(h.dtype)
    z, xbc, dt = _split_proj(p, u, n_heads, d_head, d_state)
    # pre-conv stream tail, zero-filled on the left for S < CONV_K - 1
    conv_tail = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))[:, S:, :]
    x, Bm, Cm = _split_xbc(_causal_conv(p, xbc), di, d_state)

    A = -jnp.exp(p["A_log"])
    xh = x.reshape(B, S, n_heads, d_head)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)
    _, (xh_p, dt_p, B_p, C_p) = _pad_seq(chunk, xh, dt, Bf, Cf)
    y = ops.ssd(xh_p, dt_p, A, B_p, C_p, chunk=chunk)[:, :S]
    y = y + xh * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, di)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], eps)
    out = y @ p["out_proj"].astype(h.dtype)
    cache = {
        "conv": conv_tail.astype(jnp.float32),
        "ssm": _final_state(xh, dt, A, Bf, chunk=chunk),
    }
    return out, cache


def init_mamba2_cache(batch: int, n_heads: int, d_head: int, d_state: int, dtype=jnp.float32):
    conv_dim = n_heads * d_head + 2 * d_state
    return {
        "conv": jnp.zeros((batch, CONV_K - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, n_heads, d_state, d_head), jnp.float32),
    }


def mamba2_decode(
    p: Params,
    h: jnp.ndarray,  # [B, 1, d_model]
    cache: Dict[str, jnp.ndarray],
    n_heads: int,
    d_head: int,
    d_state: int,
    eps: float = 1e-6,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    B = h.shape[0]
    di = n_heads * d_head
    u = h @ p["in_proj"].astype(h.dtype)
    z, xbc, dt = _split_proj(p, u, n_heads, d_head, d_state)
    z = z[:, 0]
    dt = dt[:, 0]  # [B, H]

    # conv cache: window = [tail, x‖B‖C]
    win = jnp.concatenate([cache["conv"], xbc[:, :1, :].astype(cache["conv"].dtype)], axis=1)
    conv = sum(win[:, i, :] * p["conv_w"][i].astype(win.dtype) for i in range(CONV_K))
    conv = jax.nn.silu(conv + p["conv_b"].astype(win.dtype))  # [B, conv_dim]
    new_conv = win[:, 1:, :]
    xc, Bm, Cm = _split_xbc(conv, di, d_state)
    Bm = Bm.astype(jnp.float32)  # [B, N]
    Cm = Cm.astype(jnp.float32)

    A = -jnp.exp(p["A_log"])  # [H]
    xh = xc.reshape(B, n_heads, d_head).astype(jnp.float32)
    dec = jnp.exp(A[None, :] * dt)  # [B, H]
    s = cache["ssm"]  # [B, H, N, P]
    s = dec[..., None, None] * s + dt[..., None, None] * (
        Bm[:, None, :, None] * xh[:, :, None, :]
    )
    # the state is kept, and read back, in the cache's dtype; the read is a
    # full-precision contraction (a TPU f32 dot would round it to bf16)
    s = s.astype(cache["ssm"].dtype)
    y = jnp.einsum(
        "bn,bhnp->bhp", Cm, s.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )  # [B, H, P]
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(B, di).astype(h.dtype)

    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], eps)
    out = (y @ p["out_proj"].astype(h.dtype)).reshape(B, 1, -1)
    return out, {"conv": new_conv, "ssm": s}
