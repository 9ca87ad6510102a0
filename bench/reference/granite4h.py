"""Plain float32 reference of granite-4.0-h-small (granitemoehybrid), in
straightforward ``jax.numpy``: a forward pass, a one-token decode step and
the layer's held-expert share.  No kernels, no chunked scans, no batching
tricks; every matrix product at full float32 precision (callers run it
under ``jax.default_matmul_precision("highest")``, as ``decode_step`` and
``forward`` do themselves).

It follows the published model (the Hugging Face ``granitemoehybrid``
modelling of ibm-granite/granite-4.0-h-small):

  x = embed[tokens] * embedding_multiplier
  per layer:  h = x + mixer(rmsnorm(x)) * residual_multiplier
              x = h + (moe(rmsnorm(h)) + shared(rmsnorm(h))) * residual_multiplier
  logits = rmsnorm(x) @ embed.T / logits_scaling

  attention (NoPE): GQA, scores q.k * attention_multiplier, causal softmax
  Mamba-2: in_proj -> z, x|B|C, dt;  causal depthwise conv (with bias) over
           x|B|C, SiLU;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
           s_t = exp(dt A) s_{t-1} + dt B_t (x) x_t;  y_t = C_t . s_t + D x_t
           y = rmsnorm(y * silu(z));  out_proj
  MoE: logits = h @ router (all experts); top-k logits; softmax over those k;
       out = sum over routed held experts of gate * (silu(h Wi) * (h Wu)) Wo

Departure, as the deployment prescribes: a chip holds experts
``offset .. offset + held - 1`` of each layer and adds only their part
(``held_share``); ``decode_step`` and ``forward`` compute that share.

Weights come in the program's parameter layout (``params['blocks']
['sub<i>']`` stacked over groups); ``m`` is a configuration file,
whose top level holds the Hugging Face config.json keys, and ``offset`` the first held
expert.  It imports nothing of the program.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, Iterator

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def layers(params: Dict, n_layers: int) -> Iterator[Dict]:
    """Per-layer parameter dicts from the program's group-stacked layout,
    sliced one layer at a time."""
    blocks = params["blocks"]
    period = len(blocks)
    for layer in range(n_layers):
        g, i = divmod(layer, period)
        yield jax.tree.map(lambda a: a[g], blocks[f"sub{i}"])


def head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


# --------------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------------- #


def route(p: Dict, h, k: int):
    """(top-k expert ids [T, k], their gates [T, k]) over all experts."""
    logits = _mm(h, p["router"])
    top, idx = jax.lax.top_k(logits, k)
    return idx, jax.nn.softmax(top, axis=-1)


def expert(p: Dict, j: int, h):
    return _mm(jax.nn.silu(_mm(h, p["wi"][j])) * _mm(h, p["wu"][j]), p["wo"][j])


def shared(p: Dict, h):
    return _mm(jax.nn.silu(_mm(h, p["shared_wi"])) * _mm(h, p["shared_wu"]), p["shared_wo"])


def held_share(p: Dict, h, k: int, offset: int):
    """The routed part of held experts ``offset .. offset + len(p['wi']) - 1``
    for tokens ``h`` [T, D] (no shared expert)."""
    idx, gates = route(p, h, k)
    out = jnp.zeros(h.shape, F32)
    for j in range(p["wi"].shape[0]):
        g = jnp.sum(jnp.where(idx == offset + j, gates, 0.0), axis=-1)
        out = out + g[:, None] * expert(p, j, h)
    return out


def held_counts(p: Dict, h, k: int, offset: int):
    """Tokens routed to each held expert [E_held] (int32)."""
    idx, _ = route(p, h, k)
    return jnp.stack([jnp.sum(idx == offset + j) for j in range(p["wi"].shape[0])]).astype(jnp.int32)


def moe(p: Dict, h, m: Dict, offset: int):
    return held_share(p, h, m["num_experts_per_tok"], offset) + shared(p, h)


# --------------------------------------------------------------------------- #
# mixers
# --------------------------------------------------------------------------- #


def _mamba_dims(m: Dict):
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    return H, P, N, H * P


def _mamba_out(p, y, z, m):
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], m["rms_norm_eps"])
    return _mm(y, p["out_proj"])


def mamba_forward(p: Dict, h, m: Dict):
    """Full sequence [B, S, D] by the plain recurrence; returns (out,
    conv tail [B, K-1, conv_dim], state [B, H, N, P])."""
    H, P, N, di = _mamba_dims(m)
    K = m["mamba_d_conv"]
    B, S, _ = h.shape
    u = _mm(h, p["in_proj"])
    z, xbc, dt = u[..., :di], u[..., di:2 * di + 2 * N], u[..., 2 * di + 2 * N:]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i].astype(F32) for i in range(K))
    xbc_c = jax.nn.silu(conv + p["conv_b"].astype(F32))
    x, Bm, Cm = xbc_c[..., :di], xbc_c[..., di:di + N], xbc_c[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    xh = x.reshape(B, S, H, P)

    def step(s, t):
        x_t, b_t, c_t, dt_t = t
        s = jnp.exp(dt_t * A)[..., None, None] * s + dt_t[..., None, None] * (
            b_t[:, None, :, None] * x_t[:, :, None, :])
        return s, jnp.einsum("bn,bhnp->bhp", c_t, s, precision=HI)

    s0 = jnp.zeros((B, H, N, P), F32)
    s, y = jax.lax.scan(step, s0, (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(Bm, 1, 0),
                                   jnp.moveaxis(Cm, 1, 0), jnp.moveaxis(dt, 1, 0)))
    y = jnp.moveaxis(y, 0, 1) + xh * p["D"].astype(F32)[:, None]
    out = _mamba_out(p, y.reshape(B, S, di), z, m)
    return out, pad[:, S:], s


def mamba_decode(p: Dict, h, conv_tail, state, m: Dict):
    """One token [B, D]; returns (out, new conv tail, new state)."""
    H, P, N, di = _mamba_dims(m)
    K = m["mamba_d_conv"]
    B = h.shape[0]
    u = _mm(h, p["in_proj"])
    z, xbc, dt = u[..., :di], u[..., di:2 * di + 2 * N], u[..., 2 * di + 2 * N:]
    win = jnp.concatenate([conv_tail.astype(F32), xbc[:, None]], axis=1)  # [B, K, conv]
    conv = sum(win[:, i] * p["conv_w"][i].astype(F32) for i in range(K))
    xbc_c = jax.nn.silu(conv + p["conv_b"].astype(F32))
    x, Bm, Cm = xbc_c[..., :di], xbc_c[..., di:di + N], xbc_c[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    xh = x.reshape(B, H, P)
    s = jnp.exp(dt * A)[..., None, None] * state.astype(F32) + dt[..., None, None] * (
        Bm[:, None, :, None] * xh[:, :, None, :])
    y = jnp.einsum("bn,bhnp->bhp", Cm, s, precision=HI) + xh * p["D"].astype(F32)[:, None]
    return _mamba_out(p, y.reshape(B, di), z, m), win[:, 1:], s


def _qkv(p, h, m):
    Hq, Hk, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    lead = h.shape[:-1]
    q = _mm(h, p["wq"]).reshape(*lead, Hq, Dh)
    k = _mm(h, p["wk"]).reshape(*lead, Hk, Dh)
    v = _mm(h, p["wv"]).reshape(*lead, Hk, Dh)
    return q, k, v


def attention_forward(p: Dict, h, m: Dict):
    """Full causal NoPE attention [B, S, D]; returns (out, k, v [B, S, Hk, Dh])."""
    Hq, Hk = m["num_attention_heads"], m["num_key_value_heads"]
    B, S, _ = h.shape
    q, k, v = _qkv(p, h, m)
    g = Hq // Hk
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=HI) * m["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vv, precision=HI)
    return _mm(o.reshape(B, S, -1), p["wo"]), k, v


def attention_decode(p: Dict, h, k_cache, v_cache, cache_len: int, m: Dict, block: int = 8192):
    """One token [B, D] over ``cache_len`` cached tokens and itself.

    ``k_cache``/``v_cache`` slice to the program's [B, Hk, S_max, Dh]
    buffers (``k_cache[:, :, lo:hi]`` reads one block); the scores are taken
    in blocks of ``block`` keys with a running (online) softmax, so a long
    cache is read and upcast one block at a time."""
    Hq, Hk, Dh = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    B = h.shape[0]
    q, k_new, v_new = _qkv(p, h, m)  # [B, H, Dh]
    qg = q.reshape(B, Hk, Hq // Hk, Dh)
    scale = m["attention_multiplier"]
    mx = jnp.full((B, Hk, Hq // Hk), -jnp.inf, F32)
    den = jnp.zeros((B, Hk, Hq // Hk), F32)
    acc = jnp.zeros((B, Hk, Hq // Hk, Dh), F32)

    for lo in range(0, cache_len, block):
        hi = min(lo + block, cache_len)
        mx, den, acc = _fold(qg, scale, mx, den, acc, k_cache[:, :, lo:hi], v_cache[:, :, lo:hi])
    mx, den, acc = _fold(qg, scale, mx, den, acc, k_new[:, :, None], v_new[:, :, None])
    o = (acc / den[..., None]).reshape(B, Hq * Dh)
    return _mm(o, p["wo"]), k_new, v_new


@jax.jit
def _fold(qg, scale, mx, den, acc, kb, vb):
    """One block of keys into the running softmax (max, denominator, sum)."""
    kb, vb = kb.astype(F32), vb.astype(F32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, kb, precision=HI) * scale
    m_new = jnp.maximum(mx, s.max(axis=-1))
    w = jnp.exp(s - m_new[..., None])
    a = jnp.exp(mx - m_new)
    return (m_new, den * a + w.sum(-1),
            acc * a[..., None] + jnp.einsum("bhgk,bhkd->bhgd", w, vb, precision=HI))


# --------------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------------- #


class _Slice:
    """Indexes one layer of a stacked cache without copying the layer."""

    def __init__(self, get):
        self._get = get

    def __getitem__(self, idx):
        return self._get(idx)


def _embed(params, tokens, m):
    return params["embed"][tokens].astype(F32) * m["embedding_multiplier"]


def _logits(params, x, m):
    xn = rmsnorm(x, params["final_norm"], m["rms_norm_eps"])
    return _mm(xn, params["embed"].T) / m["logits_scaling"]


def forward(params: Dict, tokens, m: Dict, offset: int = 0):
    """Logits [B, S, V] of a whole sequence."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params, tokens, m)
        r, eps = m["residual_multiplier"], m["rms_norm_eps"]
        for kind, p in zip(m["layer_types"], layers(params, len(m["layer_types"]))):
            h = rmsnorm(x, p["norm1"], eps)
            if kind == "attention":
                mix = attention_forward(p["attn"], h, m)[0]
            else:
                mix = mamba_forward(p["mamba"], h, m)[0]
            x = x + mix * r
            h = rmsnorm(x, p["norm2"], eps)
            B, S, D = h.shape
            x = x + moe(p["moe"], h.reshape(B * S, D), m, offset).reshape(B, S, D) * r
        return _logits(params, x, m)


@functools.lru_cache(maxsize=8)
def _layer_steps(m_json: str, offset: int):
    """Each kind of layer as one jitted function of plain jnp, compiled once
    for all layers of that kind and every step of one configuration."""
    m = json.loads(m_json)
    mamba = jax.jit(lambda p, h, c, s: mamba_decode(p, h, c, s, m)[0])
    ffn = jax.jit(lambda p, h: (moe(p, h, m, offset),
                                held_counts(p, h, m["num_experts_per_tok"], offset)))
    return mamba, ffn


def decode_step(params: Dict, caches: Dict, tokens, cache_len: int, m: Dict, offset: int = 0):
    """One token per sequence (``tokens`` [B]) after ``cache_len`` cached
    ones.  ``caches`` in the program's decode layout (``kv`` {k, v}
    [G, n_attn, B, Hk, S_max, Dh], ``ssm_conv`` [G, n_mamba, B, K-1, conv],
    ``ssm_state`` [G, n_mamba, B, H, N, P]).  Returns (logits [B, V],
    held-expert counts [n_layers, E_held])."""
    mamba, ffn = _layer_steps(json.dumps(m, sort_keys=True), offset)
    with jax.default_matmul_precision("highest"):
        x = _embed(params, tokens, m)
        r, eps = m["residual_multiplier"], m["rms_norm_eps"]
        kinds = m["layer_types"]
        period = len(params["blocks"])
        counts = []
        for l, (kind, p) in enumerate(zip(kinds, layers(params, len(kinds)))):
            g, i = divmod(l, period)
            # position of this layer among its group's attention / mamba layers
            nth = sum(1 for j in range(i) if kinds[g * period + j] == kind)
            h = rmsnorm(x, p["norm1"], eps)
            if kind == "attention":
                k_all, v_all = caches["kv"]["k"], caches["kv"]["v"]
                kb = _Slice(lambda idx: k_all[(g, nth) + idx])
                vb = _Slice(lambda idx: v_all[(g, nth) + idx])
                mix = attention_decode(p["attn"], h, kb, vb, cache_len, m)[0]
            else:
                mix = mamba(p["mamba"], h, caches["ssm_conv"][g, nth], caches["ssm_state"][g, nth])
            x = x + mix * r
            out, c = ffn(p["moe"], rmsnorm(x, p["norm2"], eps))
            counts.append(c)
            x = x + out * r
        return _logits(params, x, m), jnp.stack(counts)

