"""Entry ``attach``: ``CXLMemSim.attach`` around a real jitted train step.

One client call is ``AttachedProgram.step``: the step's epoch batch is
submitted to the analysis engine, the user's train step runs natively on
the same chip, and the pricing folds into the report asynchronously.
"""

from __future__ import annotations

from typing import Dict

import adapt
import compare
import harness
import tenants
from reference import cell, oracle


def traffic(wl: Dict, seed: int):
    mix = tenants.draw(harness.load_json("traffic", wl["traffic"] + ".json"), seed)
    if len(mix) != 1 or mix[0]["kind"] != "train":
        raise ValueError("attach prices one training program")
    return mix


def reference(cfg: Dict, wl: Dict, seed: int) -> cell.Batch:
    """What each step should fold: the train step's layer epochs on the
    configuration's fabric."""
    return cell.tenant_batch(cfg, tenants.programs(cfg, traffic(wl, seed)))


class Entry:
    CALL_SPAN = "bench.step"
    # DispatchStats fields this path fills (the pipeline dispatch)
    FILLED = ("stage_s", "transfer_s", "compile_s", "compute_s", "lowerings")

    def __init__(self, cfg: Dict, wl: Dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.core import ClassMapPolicy, CXLMemSim, EpochSchedule
        from repro.launch.steps import make_train_step
        from repro.models import Model, ModelConfig
        from repro.optim.adamw import AdamWConfig, adamw_init

        self.cfg, self.wl = cfg, wl
        m, sim = cfg["model"], cfg["simulator"]
        mc = ModelConfig(
            name=cfg["name"], family="dense", n_layers=m["num_hidden_layers"],
            d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
            d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
            rope_theta=float(m["rope_theta"]), qk_norm=bool(m["qk_norm"]),
            mlp_gated=bool(m["mlp_gated"]), tie_embeddings=bool(m["tie_word_embeddings"]),
        )
        opt_cfg = AdamWConfig(**cfg["optimizer"])
        self.mix = traffic(wl, seed)
        batch, seq = self.mix[0]["batch"], self.mix[0]["seq"]
        step_fn = make_train_step(mc, opt_cfg)
        model = Model(mc)

        def init(key):
            k_w, k_tok = jax.random.split(key)
            params = model.init(k_w)
            opt = {"adam": adamw_init(params, opt_cfg), "ef": {}}
            tokens = jax.random.randint(k_tok, (batch, seq), 0, mc.vocab_size)
            return params, opt, {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

        key = jax.random.PRNGKey(0)
        for w in tenants.seed_words(seed):
            key = jax.random.fold_in(key, w)
        with harness.span("bench.init"):
            self.params, self.opt, self.data = jax.jit(init)(key)
        with harness.span("bench.compile"):
            compiled = (
                jax.jit(step_fn, donate_argnums=(0, 1))
                .lower(self.params, self.opt, self.data)
                .compile()
            )

        def user_step(params, opt, data):
            with harness.span("bench.tenant_step"):
                return compiled(params, opt, data)

        (self.regions, self.phases), = tenants.programs(cfg, self.mix)
        rmap, phases = adapt.memory_program(self.regions, self.phases)
        topo = adapt.topology(cfg["fabric"], 1)
        policy = ClassMapPolicy(cfg["placement"], granularity_bytes=sim["granularity_bytes"])
        self.sim = CXLMemSim(
            topo, policy, epoch=EpochSchedule(sim["epoch"]), n_windows=sim["n_windows"],
            max_events_per_access=sim["max_events_per_access"],
            pipeline=bool(sim["pipeline"]), warmup=bool(sim["warmup"]),
        )
        with harness.span("bench.attach"):
            self.prog = self.sim.attach(user_step, phases, rmap)
        self.ref = cell.tenant_batch(cfg, [(self.regions, self.phases)])
        self.events_per_call = self.ref.events_per_call
        self.hosts, self.qos_on = self.ref.hosts, self.ref.qos_on
        for _ in range(int(wl.get("warm_calls", 2))):
            self.call()
        self.flush()
        jax.block_until_ready(self.params)

    def call(self) -> None:
        self.params, self.opt, self.metrics = self.prog.step(self.params, self.opt, self.data)

    def flush(self) -> None:
        import jax

        self.prog.flush()
        jax.block_until_ready(self.metrics["loss"])

    def snapshot(self) -> Dict:
        rep = self.prog.report
        return {
            "report": adapt.report_ns(rep, hosts=False),
            "calls": rep.steps,
            "epochs": rep.epochs,
            "dropped": rep.dropped_batches,
            "native_s": rep.native_s,
            "stage_s": rep.stage_s,
            "transfer_s": rep.transfer_s,
            "compile_s": rep.compile_s,
            "compute_s": rep.compute_s,
        }

    def expected(self, q=oracle.exact) -> Dict:
        """One call's pricing by the reference."""
        return self.ref.expected(q)

    def readings(self, win, ref: Dict) -> Dict[str, float]:
        return compare.window_readings(win, ref, self.ref.epochs_per_call)

    def close(self) -> None:
        self.prog.close()
        del self.params, self.opt, self.data
