"""Per-architecture smoke tests: reduced config, one forward + one train step
on CPU, asserting output shapes and no NaNs (assignment requirement)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.launch.steps import make_train_step
from repro.models import Model
from repro.optim.adamw import AdamWConfig, adamw_init

B, S = 2, 64


def _inputs(cfg, key):
    if cfg.embed_inputs:
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        return {"tokens": toks, "labels": toks}
    emb = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    lab = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return {"embeds": emb, "labels": lab}


@pytest.mark.parametrize("arch", cfgs.ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = dataclasses.replace(
        cfgs.get_smoke(arch), dtype=jnp.float32, cache_dtype=jnp.float32
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _inputs(cfg, jax.random.PRNGKey(1))

    # forward
    inp = batch["tokens"] if cfg.embed_inputs else batch["embeds"]
    logits, aux = model.forward(params, inp)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), "NaN in forward logits"

    # one full train step (grad + AdamW)
    opt_cfg = AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=1)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    opt_state = {"adam": adamw_init(params, opt_cfg), "ef": {}}
    new_params, new_opt, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"])), "non-finite loss"
    assert int(new_opt["adam"]["step"]) == 1
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).sum()),
            params, new_params,
        ),
    )
    assert delta > 0, "optimizer made no update"


@pytest.mark.parametrize(
    "arch", [a for a in cfgs.ARCH_IDS if cfgs.get_config(a).causal]
)
def test_smoke_prefill_decode_roundtrip(arch):
    cfg = dataclasses.replace(
        cfgs.get_smoke(arch),
        dtype=jnp.float32,
        cache_dtype=jnp.float32,
        # lossless capacity so MoE decode matches forward exactly
        capacity_factor=float(max(cfgs.get_smoke(arch).n_experts, 1)),
        decode_capacity_factor=float(max(cfgs.get_smoke(arch).n_experts, 1)),
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if cfg.embed_inputs:
        inp = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    else:
        inp = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model), jnp.float32)

    logits, _ = model.forward(params, inp)
    _, caches, clen = model.prefill(params, inp[:, : S - 1], pad_to=S + 4)
    lg_dec, new_caches = model.decode_step(params, caches, inp[:, S - 1 : S], clen)
    ref = logits[:, -1].astype(jnp.float32)
    got = lg_dec.astype(jnp.float32)
    rel = float(jnp.abs(ref - got).max()) / (float(jnp.abs(ref).max()) + 1e-9)
    assert rel < 5e-4, f"decode diverges from forward: rel={rel}"
    # cache structurally unchanged
    assert jax.tree.structure(caches) == jax.tree.structure(new_caches)


def test_cells_accounting():
    cells = cfgs.cells()
    assert len(cells) == 44
    runnable = [c for c in cells if c["runnable"]]
    assert len(runnable) == 35
    for c in cells:
        if not c["runnable"]:
            assert c["skip"]


@pytest.mark.parametrize("arch", cfgs.ARCH_IDS)
def test_input_specs_abstract(arch):
    """input_specs must be pure ShapeDtypeStructs (no allocation)."""
    cfg = cfgs.get_config(arch)
    for sname, shape in cfgs.SHAPES.items():
        spec = cfgs.input_specs(cfg, shape)
        for leaf in jax.tree.leaves(spec):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_param_counts_hit_targets():
    targets = {
        "mistral-large-123b": (123e9, 0.05),
        "chatglm3-6b": (6e9, 0.10),
        "starcoder2-3b": (3e9, 0.10),
        "qwen3-0.6b": (0.6e9, 0.15),
        "granite-moe-3b-a800m": (3.3e9, 0.10),
        "llama4-maverick-400b-a17b": (400e9, 0.05),
        "jamba-v0.1-52b": (52e9, 0.05),
        "mamba2-2.7b": (2.7e9, 0.05),
        "qwen2-vl-72b": (72e9, 0.05),
        "hubert-xlarge": (1e9, 0.15),
        "granite-4.0-h-small": (32.2e9, 0.03),
    }
    for arch, (want, tol) in targets.items():
        got = cfgs.get_config(arch).param_counts()["total"]
        assert abs(got - want) / want < tol, f"{arch}: {got/1e9:.2f}B vs {want/1e9}B"
