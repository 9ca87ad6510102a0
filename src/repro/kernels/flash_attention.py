"""Pallas TPU flash attention (forward), GQA + causal.

VMEM-tiled online-softmax attention.  Grid is (B, H, nQ, nK) with the KV
axis innermost: the TPU executes the grid sequentially, so the running
(max, sum, accumulator) state for one Q tile lives in VMEM scratch across
the KV steps and is normalized + written out on the last step.

Tiling (defaults, f32):
  q tile   (1, 1, BQ, D)  BQ = 256        ->  BQ·D·4      = 128 KiB  (D=128)
  k/v tile (1, 1, BK, D)  BK = 512        ->  2·BK·D·4    = 512 KiB
  acc      (BQ, D) f32 + m/l (BQ, 128)    ->  ~260 KiB
  total ≈ 0.9 MiB of ~16 MiB VMEM — leaves headroom for double buffering.

MXU alignment: BQ, BK, D are multiples of 128 (8·128 sublane×lane tiles,
128×128 systolic matmuls).  GQA is handled in the BlockSpec index maps:
the KV head index is ``h // (H // Hk)``, so no repeated KV materialization
(the oracle's jnp.repeat) ever touches memory.

Causality: KV tiles entirely above the diagonal are skipped with
``pl.when`` — for long sequences this halves the work, and because it is a
grid-step predicate the skipped tiles still advance the sequential grid
without touching the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG = -1e30


def _kernel(
    q_ref, k_ref, v_ref, qoff_ref, out_ref, acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    q_offset = qoff_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this q/k tile
    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    # skip tiles strictly above the causal diagonal
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(jnp.asarray(run) if isinstance(run, bool) else run)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # [BQ, D]
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # [BK, D]
        v = v_ref[0, 0, :, :].astype(jnp.float32)  # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_ref[:, 0]  # [BQ]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)  # [BQ]
        p = jnp.exp(s - m_cur[:, None])  # [BQ, BK]
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, 0] = m_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        # rows with no visible keys (fully masked) produce 0, not NaN
        denom = jnp.where(l > 0, l, 1.0)
        out_ref[0, 0, :, :] = (acc_ref[...] / denom[:, None]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, Hk, Sk, D]
    v: jnp.ndarray,  # [B, Hk, Sk, D]
    q_offset: int | jnp.ndarray = 0,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    assert H % Hk == 0, "GQA requires H % Hk == 0"
    group = H // Hk
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k

    qoff = jnp.asarray([q_offset], jnp.int32)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_offset scalar
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, qoff)
