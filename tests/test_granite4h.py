"""granite-4.0-h-small (granitemoehybrid) at SMOKE size on the CPU: the model
against the plain float32 reference in ``bench/reference/granite4h.py``, the
held-expert share, the decode step's routing counts, the routing-driven
decode memory program, and attach with a program built from each step's
outputs against the f64 oracle."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.core import ClassMapPolicy, CXLMemSim, EpochSchedule, figure1_topology
from repro.core.analyzer import analyze_ref
from repro.core.spans import reset, traced_totals
from repro.models import Model
from repro.models.moe import held_moe_block
from repro.models.phases import decode_program, layer_param_counts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "granite4h_ref", os.path.join(ROOT, "bench", "reference", "granite4h.py")
)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

SMOKE = cfgs.get_smoke("granite-4.0-h-small")
# the system in float32, so that what is compared is the computation and not
# the serving precision
F32 = dataclasses.replace(SMOKE, dtype=jnp.float32, cache_dtype=jnp.float32)
B, S = 2, 24

# Tolerances.  Both sides compute in float32 on the CPU; they differ only in
# the order of float32 sums: the system's SSD scan runs in chunks and its
# attention with an online softmax in blocks, the reference runs the plain
# recurrence and one softmax.  Over ten layers that reassociation stays
# below 1e-5 of the largest logit; 1e-4 leaves a decade of room.
LOGIT_RTOL = 1e-4


def model_block(cfg):
    """The reference's view of a configuration (Hugging Face keys)."""
    return {
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.d_head,
        "mamba_n_heads": cfg.ssm_heads,
        "mamba_d_head": cfg.ssm_d_head,
        "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": 4,
        "num_experts_per_tok": cfg.top_k,
        "layer_types": ["attention" if m == "attn" else "mamba"
                        for _ in range(cfg.n_groups) for m, _ in cfg.group_spec()],
        "attention_multiplier": cfg.attention_multiplier,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "rms_norm_eps": cfg.norm_eps,
    }


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.fixture(scope="module")
def setup():
    model = Model(F32)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, F32.vocab_size)
    return model, params, tokens


def test_smoke_keeps_the_period_and_the_held_share():
    kinds = [m for m, _ in SMOKE.group_spec()]
    assert kinds == ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    assert all(f == "moe" for _, f in SMOKE.group_spec())
    assert SMOKE.n_held_experts * 9 == SMOKE.n_experts  # 8 of 72 -> 2 of 18
    full = cfgs.get_config("granite-4.0-h-small")
    assert [m for m, _ in full.group_spec()] == kinds
    assert full.n_layers == 40 and full.n_held_experts == 72


def test_forward_matches_reference(setup):
    model, params, tokens = setup
    got, _ = model.forward(params, tokens)
    want = ref.forward(params, tokens, model_block(F32), offset=F32.expert_offset)
    assert _rel(got, want) < LOGIT_RTOL


def test_prefill_then_decode_matches_reference_forward(setup):
    """Prefill S0 tokens, then decode the rest one at a time through the
    cache: every position's logits match the reference's full forward."""
    model, params, tokens = setup
    want = ref.forward(params, tokens, model_block(F32), offset=F32.expert_offset)
    s0 = 5
    logits, caches, clen = model.prefill(params, tokens[:, :s0], pad_to=S)
    assert _rel(logits, want[:, s0 - 1]) < LOGIT_RTOL
    step = jax.jit(model.decode_step)
    for t in range(s0, S):
        logits, caches = step(params, caches, tokens[:, t:t + 1], jnp.int32(t))
        assert _rel(logits, want[:, t]) < LOGIT_RTOL, t


def test_decode_step_matches_reference_decode_step(setup):
    """The reference decode step, on the same cache, gives the system's
    logits and counts (the attention taken in blocks of 8 keys)."""
    model, params, tokens = setup
    m = model_block(F32)
    _, caches, clen = model.prefill(params, tokens[:, :-1], pad_to=S + 4)
    logits, _, counts = model.decode_step(params, caches, tokens[:, -1:], clen, expert_counts=True)
    want, want_counts = ref.decode_step(params, caches, tokens[:, -1], int(clen), m)
    assert _rel(logits, want) < LOGIT_RTOL
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))


def test_held_shares_add_up_to_the_uncut_layer():
    """Nine chips' held-expert shares, with the shared expert counted once,
    add up to what the uncut reference layer gives (to float32 rounding of
    a sum of nine parts)."""
    full = dataclasses.replace(F32, experts_held=0)
    p = Model(full).init(jax.random.PRNGKey(2))["blocks"]["sub0"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, 3, full.d_model), jnp.float32)
    n_held = SMOKE.n_held_experts
    total = jnp.zeros_like(h)
    for chip in range(full.n_experts // n_held):
        lo = chip * n_held
        share = {"router": p["router"], "wi": p["wi"][lo:lo + n_held],
                 "wu": p["wu"][lo:lo + n_held], "wo": p["wo"][lo:lo + n_held]}
        if chip == 0:  # every chip computes the shared expert alike: count it once
            share.update({k: v for k, v in p.items() if k.startswith("shared_")})
        out, _, _ = held_moe_block(share, h, full.top_k, expert_offset=lo)
        total = total + out
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, h.reshape(-1, full.d_model), model_block(full), 0)
    assert _rel(total.reshape(-1, full.d_model), want) < 1e-5


def test_decode_counts_are_the_held_bincount_of_the_reference_top_k(setup):
    model, params, tokens = setup
    m = model_block(F32)
    _, caches, clen = model.prefill(params, tokens[:, :-1], pad_to=S)
    _, _, counts = model.decode_step(params, caches, tokens[:, -1:], clen, expert_counts=True)
    counts = np.asarray(counts)
    assert counts.shape == (F32.n_layers, F32.n_held_experts) and counts.dtype == np.int32
    # rebuild each layer's router input with the reference, then bincount its top-k
    _, want = ref.decode_step(params, caches, tokens[:, -1], int(clen), m)
    np.testing.assert_array_equal(counts, np.asarray(want))
    assert counts.sum() <= B * F32.top_k * F32.n_layers


def test_decode_program_regions_and_routing():
    cfg = SMOKE
    batch, s_max = 4, 64
    regions, program = decode_program(cfg, batch, s_max, param_dtype_bytes=2,
                                      kv_dtype_bytes=2, state_dtype_bytes=4)
    classes = {r.tensor_class for r in regions}
    assert {"expert", "ssm_state", "kvcache", "param"} <= classes
    # weight regions carry param_shapes()' counts at 2 bytes a parameter
    shapes = cfg.param_shapes()
    blocks = shapes["blocks"]
    n = lambda tree: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(tree))  # noqa: E731
    for layer in range(cfg.n_layers):
        sub = blocks[f"sub{layer % cfg.group_size}"]
        moe = sub["moe"]
        assert regions[f"L{layer}.router"].nbytes == 2 * n(moe["router"])
        assert regions[f"L{layer}.mixer"].nbytes == 2 * n({k: v for k, v in sub.items() if k != "moe"})
        shared = n([moe[k] for k in ("shared_wi", "shared_wu", "shared_wo")])
        assert regions[f"L{layer}.shared"].nbytes == 2 * shared
        per_expert = n([moe["wi"], moe["wu"], moe["wo"]]) // cfg.n_held_experts
        for j in range(cfg.n_held_experts):
            r = regions[f"L{layer}.expert{j}"]
            assert r.tensor_class == "expert" and r.nbytes == 2 * per_expert
        kind = cfg.group_spec()[layer % cfg.group_size][0]
        assert (f"L{layer}.kv" in regions) == (kind == "attn")
        assert (f"L{layer}.ssm" in regions) == (kind == "mamba")
    total = sum(r.nbytes for r in regions if r.tensor_class in ("param", "expert"))
    embed = cfg.padded_vocab * cfg.d_model
    assert total == 2 * (n(blocks) + embed + cfg.d_model)
    assert layer_param_counts(cfg)[5]["mixer"] != layer_param_counts(cfg)[0]["mixer"]

    counts = np.zeros((cfg.n_layers, cfg.n_held_experts), np.int32)
    counts[3, 1] = 2
    cache_len = 40
    phases = program(cache_len, counts)
    assert [p.name for p in phases] == ["embed"] + [f"L{i}" for i in range(cfg.n_layers)] + ["head"]
    expert_reads = [(p.name, a.region) for p in phases for a in p.accesses if "expert" in a.region]
    assert expert_reads == [("L3", "L3.expert1")]  # an unrouted expert gets no access
    kv_tok = 2 * cfg.n_kv_heads * cfg.d_head * 2
    attn = phases[1 + 5]
    kv = [(a.bytes_, a.is_write) for a in attn.accesses if a.region == "L5.kv"]
    assert kv == [(batch * cache_len * kv_tok, False), (batch * kv_tok, True)]
    ssm = [(a.bytes_, a.is_write) for a in phases[1].accesses if a.region == "L0.ssm"]
    assert ssm == [(regions["L0.ssm"].nbytes, False), (regions["L0.ssm"].nbytes, True)]


def _sim():
    return CXLMemSim(
        figure1_topology(), ClassMapPolicy({"expert": "cxl_pool2"}),
        epoch=EpochSchedule("layer"), pipeline=True, warmup=True,
    )


def _decode_attach(sim, batch=2, s_max=48, start=30, steps=4):
    """The SMOKE model's decode step under attach with its routing-driven
    program; returns (report, per-step (cache_len, counts), regions)."""
    cfg = SMOKE
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    caches = model.init_caches(batch, s_max)
    caches = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(4), a.shape, jnp.float32).astype(a.dtype),
        caches,
    )
    regions, build = decode_program(cfg, batch, s_max)

    @jax.jit
    def step(params, caches, token, cache_len):
        logits, caches, counts = model.decode_step(params, caches, token, cache_len, expert_counts=True)
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32), caches, counts, cache_len + 1

    seen = []

    def program(out):
        counts, next_len = np.asarray(out[2]), int(out[3])
        seen.append((next_len - 1, counts))
        return build(next_len - 1, counts)

    warm = [build(start, np.full((cfg.n_layers, cfg.n_held_experts), k))
            for k in range(2)]
    prog = sim.attach(step, program, regions, warm_programs=warm)
    token = jnp.zeros((batch, 1), jnp.int32)
    clen = jnp.int32(start)
    for _ in range(steps):
        token, caches, _, clen = prog.step(params, caches, token, clen)
    rep = prog.report
    prog.close()
    return rep, seen, regions, build


def test_routed_attach_agrees_with_the_oracle_step_by_step():
    sim = _sim()
    reset()
    with jax.profiler.trace(os.path.join(os.environ.get("TMPDIR", "/tmp"), "g4h-trace")):
        rep, seen, regions, build = _decode_attach(sim)
    totals = traced_totals()
    assert rep.steps == len(seen) == 4
    assert [c for c, _ in seen] == [30, 31, 32, 33]
    lat = cong = bw = 0.0
    held = touched = 0
    for cache_len, counts in seen:
        from repro.core.tracer import synthesize_step_trace

        sim.policy.place(regions, sim.flat)
        traces, _, _ = synthesize_step_trace(build(cache_len, counts), regions, epoch_mode="layer")
        for tr in traces:
            span = max(float(tr.t_ns.max()) + 1.0, 10_000.0)
            bd = analyze_ref(sim.flat, tr, bw_window_ns=span / sim.n_windows)
            lat += bd.latency_ns
            cong += bd.congestion_ns
            bw += bd.bandwidth_ns
        held += counts.size
        touched += int((counts > 0).sum())
    assert rep.latency_s * 1e9 == pytest.approx(lat, rel=1e-5)
    assert rep.congestion_s * 1e9 == pytest.approx(cong, rel=1e-4, abs=1e-3)
    assert rep.bandwidth_s * 1e9 == pytest.approx(bw, rel=1e-4, abs=1e-3)
    assert rep.epochs == 4 * (SMOKE.n_layers + 2)
    assert totals["cxlsim.program"][0] == 4
    assert totals["cxlsim.experts.held"][0] == held
    assert totals["cxlsim.experts.touched"][0] == touched
    assert 0 < totals["cxlsim.bytes.expert"][0] < totals["cxlsim.bytes.priced"][0]


def test_static_program_keeps_its_pricing_bitwise():
    """A static program attached as a list, and the same program returned by
    a function of each step's outputs, fold bitwise the same report."""
    regions, build = decode_program(SMOKE, 2, 48)
    phases = build(20, np.ones((SMOKE.n_layers, SMOKE.n_held_experts), np.int32))
    step = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4,))
    reps = []
    for prog_arg in (phases, lambda out: phases):
        sim = _sim()
        prog = sim.attach(step, prog_arg, regions,
                          warm_programs=() if isinstance(prog_arg, list) else [phases])
        for _ in range(3):
            prog.step(x)
        reps.append(prog.report)
        prog.close()
    a, b = reps
    for f in ("latency_s", "congestion_s", "bandwidth_s", "epochs"):
        assert getattr(a, f) == getattr(b, f), f
    np.testing.assert_array_equal(a.per_pool_latency_ns, b.per_pool_latency_ns)
    np.testing.assert_array_equal(a.per_switch_congestion_ns, b.per_switch_congestion_ns)
    np.testing.assert_array_equal(a.per_switch_bandwidth_ns, b.per_switch_bandwidth_ns)
    assert a.latency_s > 0
