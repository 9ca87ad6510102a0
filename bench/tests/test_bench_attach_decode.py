"""The ``attach_decode`` entry at test size, on the CPU: its program copy
equals today's program, an unbroken run comes out correct, and runs with a
planted fault come out not correct.  The tiny tree is ``minibench``'s with
one more configuration, mix and cell."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)

import costs_decode  # noqa: E402
import decode_program  # noqa: E402
import minibench  # noqa: E402

CELL = "tiny.decode"

TINY_MODEL = {
    "num_hidden_layers": 10, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 32,
    "intermediate_size": 32, "shared_intermediate_size": 64, "vocab_size": 512,
    "num_local_experts": 2, "num_experts_per_tok": 3,
}


def tiny_config():
    with open(os.path.join(BENCH, "configs", "fig1-granite-4.0-h-small.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-granite"
    cfg.update(TINY_MODEL)
    cfg["expert_parallel"].update(experts_per_chip=2)
    # the tiny run compares a float32 program with the float32 reference, so
    # that its logits limit can be tight and every fault shows
    cfg["precision"].update(params="float32", activations="float32", kv="float32")
    return cfg


# the program prices no read of the first held expert it would read in a step
DROPPED = """
from repro.models import phases
_program_of = phases.decode_program
def decode_program(*a, **k):
    regions, build = _program_of(*a, **k)
    def program(cache_len, counts):
        out, dropped = [], False
        for ph in build(cache_len, counts):
            acc = []
            for x in ph.accesses:
                if ".expert" in x.region and not dropped:
                    dropped = True
                    continue
                acc.append(x)
            out.append(phases.Phase(ph.name, ph.flops, tuple(acc)))
        return out
    return regions, program
phases.decode_program = decode_program
"""

# the model returns the counts of the held experts shifted by one expert
# (its logits stay right, and the program is priced from the counts it
# returned)
WRONG_COUNTS = """
from repro.models import moe
_held = moe.held_moe_block
def held_moe_block(p, x, top_k, expert_offset=0):
    out, aux, _ = _held(p, x, top_k, expert_offset)
    return out, aux, _held(p, x, top_k, expert_offset + 1)[2]
moe.held_moe_block = held_moe_block
"""

# the program is built from other counts than the step returned
PROGRAM_COUNTS = """
from repro.models import phases
_program_of = phases.decode_program
def decode_program(*a, **k):
    regions, build = _program_of(*a, **k)
    return regions, lambda cache_len, counts: build(cache_len, counts + 1)
phases.decode_program = decode_program
"""

# attention scores at 1/sqrt(head_dim) instead of the 1/128 multiplier
UNSCALED = """
from repro.models import transformer
transformer._attn_scale = lambda cfg: None
"""

FAULTS = {"dropped_expert_read": DROPPED, "wrong_counts": WRONG_COUNTS,
          "program_counts": PROGRAM_COUNTS, "attention_unscaled": UNSCALED}

# the cell's pricing limits; the tiny program runs in float32 against the
# float32 reference, so its logits and counts agree to rounding
TINY_LIMITS = dict(minibench.LIMITS, logits_gap=1e-4, counts_gap=0)
PRICING = ("latency_gap", "congestion_gap", "bandwidth_gap", "epochs_gap")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = minibench.make_tree(str(tmp_path_factory.mktemp("bench")))
    b = os.path.join(root, "bench")

    def dump(obj, *parts):
        with open(os.path.join(b, *parts), "w") as f:
            json.dump(obj, f)

    dump(tiny_config(), "configs", "tiny-granite.json")
    dump({"loop": "closed", "tenants": [
        {"kind": "decode", "batch": 2, "cache_len": 24, "max_cache_len": 256}]},
        "traffic", "tiny-decode.json")
    dump({"config": "tiny-granite", "traffic": "tiny-decode", "entry": "attach_decode",
          "chips": 1, "warm_calls": 1,
          "limits": TINY_LIMITS}, "workloads", CELL + ".json")
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["workloads"].append({"name": CELL, "config": "tiny-granite", "traffic": "tiny-decode",
                             "chips": 1, "why": "test"})
    with open(man_path, "w") as f:
        json.dump(man, f)
    return root


def test_program_copy_equals_the_program():
    """The benchmark's decode program equals ``phases.decode_program`` on
    the same widths: regions, and phases for a step's counts."""
    import jax

    sys.path.insert(0, os.path.join(BENCH, "entries"))
    import attach_decode

    from repro.models.phases import decode_program as program_of

    cfg = tiny_config()
    mc = attach_decode.model_config(cfg)
    rmap, build = program_of(mc, 3, 40)
    regions, bench_build = decode_program.build(cfg, 3, 40)
    assert [(r.name, r.nbytes, r.tensor_class) for r in rmap] == regions
    counts = np.random.default_rng(0).integers(0, 3, size=(mc.n_layers, mc.n_held_experts))
    got = [(p.name, p.flops, tuple((a.region, a.bytes_, a.is_write) for a in p.accesses))
           for p in build(17, counts)]
    assert got == bench_build(17, counts.tolist())
    # every held weight once, plus KV at cache_len and the states' reads and writes
    params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(mc.param_shapes()))
    least = costs_decode.step_least_bytes(cfg, 3, 17)
    kv_tok = 2 * mc.n_kv_heads * mc.d_head * 2  # K and V, bf16
    kv = 3 * 17 * kv_tok + 3 * kv_tok
    ssm = 2 * 9 * 3 * (mc.ssm_heads * mc.ssm_state * mc.ssm_d_head + 3 * (
        mc.ssm_heads * mc.ssm_d_head + 2 * mc.ssm_state)) * 4  # read and written, f32
    embed_rows = 3 * mc.d_model * 2
    assert least == 2 * params + kv + ssm + embed_rows


def test_unbroken_run_is_correct(tree):
    rc, res, err = minibench.run(tree, CELL, seed=2**33 + 5, seconds=0.5, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["logits_gap"]["value"] < 1e-4
    assert res["checks"]["counts_gap"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    metrics = res["metrics"]
    for name in ("program_ns_per_event.events", "experts_touched.events",
                 "expert_bytes_share.events", "native_ms.events", "decode_hbm_roofline.events",
                 "window_compiles.events", "window_lowerings.events", "slot_fill.events"):
        assert name in metrics, (name, sorted(metrics))
    assert 0 < metrics["experts_touched.events"]["value"] <= 100
    assert 0 < metrics["expert_bytes_share.events"]["value"] < 100
    assert metrics["window_lowerings.events"]["value"] == 0


def test_control_fails_where_the_program_passes(tree):
    """At test size the program runs in float32 and its control
    (``bench/control_decode.py``: weights rounded to bfloat16 after set-up)
    fails the harness's own ``correct`` by ``logits_gap``, not by the
    pricing gaps."""
    rc, res, err = minibench.run(tree, CELL, seed=3, seconds=0.5,
                                 fault="import control_decode\ncontrol_decode.install()")
    assert rc == 0, err[-3000:]
    checks = res["checks"]
    assert res["correct"] is False, checks
    assert checks["logits_gap"]["value"] > checks["logits_gap"]["limit"], checks
    for name in PRICING:
        assert checks[name]["value"] <= checks[name]["limit"], (name, checks)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_run_is_not_correct(tree, fault):
    rc, res, err = minibench.run(tree, CELL, seed=11, seconds=0.5, fault=FAULTS[fault])
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]
