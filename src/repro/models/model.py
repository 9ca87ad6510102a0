"""Unified model: config, init, train forward/loss, prefill, decode.

One :class:`ModelConfig` describes every assigned architecture; the family
field selects the group structure (see :mod:`repro.models.transformer`).
All step functions are pure (params explicit) and jit/pjit-able; the
trainer and launcher compose them with sharding rules.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import transformer as tf
from .layers import rms_norm, layer_norm

__all__ = ["ModelConfig", "Model"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'hybrid' | 'granitemoehybrid' | 'ssm' | 'vlm' | 'audio'
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0  # 0 => d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0  # expert hidden (granite: 512); 0 => d_ff
    moe_interleave: int = 1  # MoE every k-th layer
    shared_expert: bool = False
    capacity_factor: float = 1.25
    decode_capacity_factor: float = 2.0
    moe_dispatch: str = "einsum"  # 'einsum' | 'dense'
    moe_group_tokens: int = 4096  # GShard dispatch group size
    # expert parallelism's share: this layer holds experts_held of the
    # n_experts the router scores, from expert_offset (0 => all of them)
    experts_held: int = 0
    expert_offset: int = 0
    shared_d_ff: int = 0  # shared-expert hidden; 0 => moe_d_ff
    # --- attention ---
    rope_variant: str = "rope"  # 'rope' | 'rope2d' | 'mrope' | 'none'
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    causal: bool = True
    window: Optional[int] = None  # sliding-window span (attn layers)
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    attention_multiplier: float = 0.0  # score scale; 0 => d_head ** -0.5
    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_d_head: int = 64
    ssm_chunk: int = 128
    attn_every: int = 0  # hybrid: 1 attn sublayer per group of this size
    # --- embeddings / misc ---
    tie_embeddings: bool = True
    embed_inputs: bool = True  # False: step takes precomputed embeddings
    norm: str = "rms"  # 'rms' | 'ln'
    norm_eps: float = 1e-6  # RMSNorm epsilon
    # Granite's scalar multipliers: embeddings × embedding_multiplier, each
    # residual branch × residual_multiplier, logits / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    mlp_gated: bool = True  # False: plain 2-matrix GELU MLP (StarCoder2, encoders)
    # Cast every weight matrix to cfg.dtype ONCE at step entry (instead of at
    # each use).  Under FSDP this moves the cast BEFORE the parameter
    # all-gather, so collectives move bf16 instead of f32 — a §Perf lever.
    cast_params_at_step: bool = False
    # Pad the embedding/lm_head vocab dim to a multiple of this so the vocab
    # axis shards on the model mesh axis (odd vocabs like 49155 otherwise
    # fall back to d_model sharding, whose contraction partial-sums the FULL
    # f32 logits across the model axis).  Padded columns are masked to -inf
    # before log_softmax, so the loss is bit-identical to the unpadded model.
    pad_vocab_to_multiple: int = 0
    # ZeRO-3 "gather at use": inside each scan-body group, cast the group's
    # weights to cfg.dtype and constrain them to a TP-only sharding, forcing
    # GSPMD to all-gather bf16 weights per layer instead of partial-summing
    # f32 activations against the data-sharded weight dim (§Perf cell 2/3).
    fsdp_gather_at_layer: bool = False
    dtype: Any = jnp.bfloat16
    cache_dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy_name: str = "nothing"  # 'nothing' | 'dots'
    scan_layers: bool = True

    # ------------------------------------------------------------------ #

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // max(self.n_heads, 1))
        if self.family in ("moe",) and self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)

    @property
    def n_held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def padded_vocab(self) -> int:
        m = self.pad_vocab_to_multiple
        if m and self.vocab_size % m:
            return self.vocab_size + (m - self.vocab_size % m)
        return self.vocab_size

    @property
    def remat_policy(self):
        if self.remat_policy_name == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return None  # save nothing

    def group_spec(self) -> Tuple[Tuple[str, Optional[str]], ...]:
        """((mixer, ffn), ...) for one group."""
        fam = self.family
        if fam in ("dense", "vlm", "audio"):
            return (("attn", "mlp"),)
        if fam == "moe":
            k = max(self.moe_interleave, 1)
            return tuple(
                ("attn", "moe" if i == k - 1 else "mlp") for i in range(k)
            )
        if fam == "ssm":
            return (("mamba", None if self.d_ff == 0 else "mlp"),)
        if fam in ("hybrid", "granitemoehybrid"):
            k = self.attn_every
            # attention mid-group (Jamba places it interior; Granite-4.0-H
            # at offset 5 of each period of 10)
            attn_pos = k // 2
            spec = []
            for i in range(k):
                mixer = "attn" if i == attn_pos else "mamba"
                if fam == "granitemoehybrid":  # MoE (+ shared expert) everywhere
                    ffn = "moe"
                else:
                    ffn = "moe" if (self.n_experts and i % 2 == 1) else "mlp"
                spec.append((mixer, ffn))
            return tuple(spec)
        raise ValueError(f"unknown family {fam}")

    @property
    def group_size(self) -> int:
        return len(self.group_spec())

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.group_size == 0, (
            f"{self.name}: n_layers {self.n_layers} not divisible by group "
            f"size {self.group_size}"
        )
        return self.n_layers // self.group_size

    @property
    def attn_layers_per_group(self) -> int:
        return sum(1 for m, _ in self.group_spec() if m == "attn")

    @property
    def mamba_layers_per_group(self) -> int:
        return sum(1 for m, _ in self.group_spec() if m == "mamba")

    # ------------------------------------------------------------------ #
    # parameter accounting (via eval_shape: no allocation)
    # ------------------------------------------------------------------ #

    def param_shapes(self):
        return jax.eval_shape(
            lambda: Model(self).init(jax.random.PRNGKey(0), abstract=True)
        )

    def param_counts(self) -> Dict[str, float]:
        shapes = self.param_shapes()
        total = 0
        expert = 0

        def visit(path, leaf):
            nonlocal total, expert
            n = 1
            for d in leaf.shape:
                n *= d
            total += n
            keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            if any("moe" == k for k in keys) and keys[-1] in ("wi", "wu", "wo"):
                expert += n

        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        for path, leaf in flat:
            visit(path, leaf)
        active = total
        if self.n_experts and self.top_k:
            active = total - expert * (1.0 - self.top_k / self.n_experts)
        return {"total": float(total), "active": float(active), "expert": float(expert)}

    def model_flops(self, kind: str, batch: int, seq: int) -> float:
        """MODEL_FLOPS per the brief: 6·N_active·D (train), 2·N_active·D
        (prefill), 2·N_active·B (decode; D = one token per sequence)."""
        n = self.param_counts()["active"]
        if kind == "train":
            return 6.0 * n * batch * seq
        if kind == "prefill":
            return 2.0 * n * batch * seq
        if kind == "decode":
            return 2.0 * n * batch
        raise ValueError(kind)


# --------------------------------------------------------------------------- #


class Model:
    """Functional wrapper: init + step functions for one config."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- init ---------------------------------------------------------- #

    def init(self, key, abstract: bool = False):
        cfg = self.cfg
        k_embed, k_stack, k_head, k_norm = jax.random.split(key, 4)
        params: Dict[str, Any] = {}
        if cfg.embed_inputs:
            params["embed"] = (
                jax.random.normal(k_embed, (cfg.padded_vocab, cfg.d_model), jnp.float32)
                * 0.02
            )
        params["blocks"] = tf.init_stack(k_stack, cfg)
        if cfg.norm == "ln":
            params["final_norm"] = {
                "g": jnp.ones((cfg.d_model,), jnp.float32),
                "b": jnp.zeros((cfg.d_model,), jnp.float32),
            }
        else:
            params["final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        if not cfg.tie_embeddings or not cfg.embed_inputs:
            params["lm_head"] = (
                jax.random.normal(k_head, (cfg.d_model, cfg.padded_vocab), jnp.float32)
                * 0.02
            )
        return params

    # ---- shared forward ------------------------------------------------- #

    def _positions(self, batch: int, seq: int, offset=0):
        cfg = self.cfg
        pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset  # [1, S]
        pos = jnp.broadcast_to(pos, (batch, seq))
        if cfg.rope_variant == "rope2d":
            return jnp.stack([pos, jnp.zeros_like(pos)], axis=1)  # [B, 2, S]
        if cfg.rope_variant == "mrope":
            return jnp.stack([pos, pos, pos], axis=1)  # [B, 3, S] (text stub)
        return pos

    def _embed(self, params, tokens_or_embeds):
        cfg = self.cfg
        if cfg.embed_inputs:
            x = params["embed"].astype(cfg.dtype)[tokens_or_embeds]
        else:
            x = tokens_or_embeds.astype(cfg.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        return x

    def _head(self, params, x):
        cfg = self.cfg
        xn = (
            layer_norm(x, params["final_norm"]["g"], params["final_norm"]["b"])
            if cfg.norm == "ln"
            else rms_norm(x, params["final_norm"], cfg.norm_eps)
        )
        if "lm_head" in params:
            w = params["lm_head"].astype(cfg.dtype)
        else:
            w = params["embed"].T.astype(cfg.dtype)
        logits = xn @ w  # [B, S, V_padded]
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        if cfg.padded_vocab != cfg.vocab_size:
            # mask pad columns to -inf: loss/argmax identical to unpadded
            col = jnp.arange(cfg.padded_vocab)
            logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
        return logits

    def forward(self, params, tokens_or_embeds, positions=None, block_specs=None):
        cfg = self.cfg
        x = self._embed(params, tokens_or_embeds)
        B, S = x.shape[:2]
        if positions is None:
            positions = self._positions(B, S)
        x, aux, _ = tf.apply_stack(
            params["blocks"], x, positions, cfg, block_specs=block_specs
        )
        return self._head(params, x), aux

    # ---- training loss --------------------------------------------------- #

    def loss(self, params, batch, aux_weight: float = 0.01, block_specs=None):
        """batch: {'tokens' | 'embeds', 'labels' [B,S] (-1 = masked)}."""
        inp = batch["tokens"] if self.cfg.embed_inputs else batch["embeds"]
        logits, aux = self.forward(
            params, inp, batch.get("positions"), block_specs=block_specs
        )
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        n = jnp.maximum(valid.sum(), 1)
        ce = -(ll * valid).sum() / n
        return ce + aux_weight * aux, {"ce": ce, "aux": aux}

    # ---- serving --------------------------------------------------------- #

    def prefill(self, params, tokens_or_embeds, pad_to: Optional[int] = None):
        """Returns (last_logits [B,V], caches, cache_len)."""
        cfg = self.cfg
        x = self._embed(params, tokens_or_embeds)
        B, S = x.shape[:2]
        positions = self._positions(B, S)
        x, _, caches = tf.apply_stack(
            params["blocks"], x, positions, cfg,
            collect_cache=True, cache_pad_to=pad_to or S,
        )
        logits = self._head(params, x[:, -1:, :])[:, 0]
        return logits, caches, jnp.asarray(S, jnp.int32)

    def init_caches(self, batch: int, s_max: int):
        """Zero caches for decode-from-scratch (dry-run decode shapes)."""
        cfg = self.cfg
        cache: Dict[str, Any] = {}
        na, nm = cfg.attn_layers_per_group, cfg.mamba_layers_per_group
        G = cfg.n_groups
        if na:
            shape = (G, na, batch, cfg.n_kv_heads, s_max, cfg.d_head)
            cache["kv"] = {
                "k": jnp.zeros(shape, cfg.cache_dtype),
                "v": jnp.zeros(shape, cfg.cache_dtype),
            }
        if nm:
            conv_dim = cfg.ssm_heads * cfg.ssm_d_head + 2 * cfg.ssm_state  # x‖B‖C
            cache["ssm_conv"] = jnp.zeros((G, nm, batch, 3, conv_dim), jnp.float32)
            cache["ssm_state"] = jnp.zeros(
                (G, nm, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_d_head),
                jnp.float32,
            )
        return cache

    def decode_step(self, params, caches, token_or_embed, cache_len, expert_counts=False):
        """One token for every sequence; returns (logits [B,V], new_caches),
        and with ``expert_counts`` (held-expert families) also the int32
        ``[n_layers, n_held_experts]`` count of tokens routed to each held
        expert of each layer."""
        cfg = self.cfg
        x = self._embed(params, token_or_embed)  # [B, 1, D]
        B = x.shape[0]
        positions = self._positions(B, 1, offset=cache_len)
        x, new_caches, counts = tf.decode_stack(
            params["blocks"], x, positions, caches, cache_len, cfg
        )
        logits = self._head(params, x)[:, 0]
        if expert_counts:
            if counts is None:
                raise ValueError(f"{cfg.name}: no held-expert layers to count")
            return logits, new_caches, counts.reshape(cfg.n_layers, cfg.n_held_experts)
        return logits, new_caches
