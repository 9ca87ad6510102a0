"""Entry ``attach_decode``: ``CXLMemSim.attach`` around a real jitted decode
step of a held-expert hybrid model, whose memory program is built from each
step's own routing.

One client call is ``AttachedProgram.step``: one greedy token for every
sequence, appended to the cache; the step runs natively, returns its
logits, caches and per-layer held-expert token counts, and its memory
program (routed experts read only where a token went) is then submitted
and priced while the next step runs.  After the window a few more steps are
each compared with the plain float32 reference decode step on the same
state: their logits (``logits_gap``) and their held-expert counts
(``counts_gap``); and every window step's program is re-priced by the f64
oracle from the recorded counts and cache lengths.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import adapt
import compare
import costs_decode
import decode_program
import harness
import tenants
from reference import cell, granite4h, oracle


def traffic(wl: Dict, seed: int) -> Dict:
    mix = tenants.draw(harness.load_json("traffic", wl["traffic"] + ".json"), seed)
    if len(mix) != 1 or mix[0]["kind"] != "decode":
        raise ValueError("attach_decode prices one decoding program")
    return mix[0]


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file, whose top level
    holds the published config.json keys."""
    import jax.numpy as jnp

    from repro.models import ModelConfig

    m, ep, prec = cfg, cfg["expert_parallel"], cfg["precision"]
    kinds = m["layer_types"]
    attn = [i for i, k in enumerate(kinds) if k == "attention"]
    if attn != [len(kinds) // 2] or len(kinds) != m["num_hidden_layers"]:
        raise ValueError("one period with its attention layer mid-period is what this entry runs")
    dt = lambda k: getattr(jnp, prec[k])  # noqa: E731
    return ModelConfig(
        name=cfg["name"], family="granitemoehybrid", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_head=decode_program.head_dim(m),
        d_ff=m["intermediate_size"], moe_d_ff=m["intermediate_size"],
        shared_d_ff=m["shared_intermediate_size"], vocab_size=m["vocab_size"],
        n_experts=decode_program.router_experts(cfg), experts_held=m["num_local_experts"],
        expert_offset=ep["first_held_expert"], top_k=m["num_experts_per_tok"],
        shared_expert=True, rope_variant="none",
        attention_multiplier=float(m["attention_multiplier"]),
        attn_every=len(kinds),
        ssm_state=m["mamba_d_state"], ssm_heads=m["mamba_n_heads"],
        ssm_d_head=m["mamba_d_head"], ssm_chunk=m["mamba_chunk_size"],
        norm_eps=float(m["rms_norm_eps"]),
        embedding_multiplier=float(m["embedding_multiplier"]),
        residual_multiplier=float(m["residual_multiplier"]),
        logits_scaling=float(m["logits_scaling"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        dtype=dt("activations"), cache_dtype=dt("kv"), remat=False,
    )


def drawn_counts(cfg: Dict, batch: int, rng) -> List[List[int]]:
    """Held-expert counts of one step with routing uniform over the router's
    experts: each token takes k distinct experts."""
    m = cfg
    E, k, held = decode_program.router_experts(cfg), m["num_experts_per_tok"], m["num_local_experts"]
    first = cfg["expert_parallel"]["first_held_expert"]
    out = []
    for _ in m["layer_types"]:
        c = [0] * held
        for _ in range(batch):
            for e in rng.choice(E, size=k, replace=False):
                if first <= e < first + held:
                    c[e - first] += 1
        out.append(c)
    return out


class Window:
    """The window's steps re-priced by the oracle: each recorded (cache_len,
    counts) rebuilt by the benchmark's own program builder."""

    def __init__(self, cfg: Dict, batch: int, s_max: int):
        sim = cfg["simulator"]
        self.cfg = cfg
        self.flat = oracle.flatten(cfg["fabric"], 1)
        self.regions, self.build = decode_program.build(cfg, batch, s_max)
        self.pool_of = cell.pool_of(self.regions, cfg["placement"], self.flat["pool_names"])
        self.g, self.max_ev, self.n_windows = (
            sim["granularity_bytes"], sim["max_events_per_access"], sim["n_windows"])

    def epochs(self, cache_len: int, counts) -> List[Dict]:
        return oracle.synthesize(self.regions, self.build(cache_len, counts), self.pool_of,
                                 self.g, self.max_ev)

    def events(self, cache_len: int, counts) -> int:
        return oracle.count_events(self.build(cache_len, counts), self.g, self.max_ev)

    def price(self, steps, q=oracle.exact) -> Dict:
        total, n_epochs = None, 0
        for cache_len, counts in steps:
            ep = self.epochs(cache_len, counts)
            n_epochs += sum(1 for e in ep if len(e["t"]))
            bd = oracle.price_batch(self.flat, ep, self.n_windows, q=q)
            total = bd if total is None else {k: total[k] + bd[k] for k in bd}
        return {"breakdown": total, "epochs": n_epochs}


def reference(cfg: Dict, wl: Dict, seed: int) -> cell.Batch:
    """One step's epochs with routing drawn from the seed, at the traffic's
    cache length (for ``control.py``: the oracle against its bfloat16 copy)."""
    t = traffic(wl, seed)
    win = Window(cfg, t["batch"], t["max_cache_len"])
    counts = drawn_counts(cfg, t["batch"], np.random.default_rng(tenants.seed_words(seed)))
    return cell.Batch(win.flat, win.epochs(t["cache_len"], counts), win.n_windows)


def serving_params(params, weight_dtype, other_dtype):
    """Weight matrices (the embedding and every block leaf of rank >= 3,
    stacked over groups) in ``weight_dtype``; norms, biases and the SSM's
    per-head vectors in ``other_dtype``."""
    import jax

    def cast(path, a):
        matrix = a.ndim >= 3 or (a.ndim == 2 and path[0].key == "embed")
        return a.astype(weight_dtype if matrix else other_dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


def draw_state(model, cfg_model, batch: int, s_max: int, cache_len: int, key,
               weight_dtype=None):
    """Weights (bf16 for serving), a starting cache filled to ``cache_len``
    and the first tokens, all from ``key``."""
    import jax
    import jax.numpy as jnp

    k_w, k_kv, k_st, k_tok = jax.random.split(key, 4)
    params = serving_params(model.init(k_w), weight_dtype or cfg_model.dtype, cfg_model.dtype)
    shapes = jax.eval_shape(lambda: model.init_caches(batch, s_max))
    keys = iter(jax.random.split(k_kv, 2))
    kv = {}
    for name in ("k", "v"):
        s = shapes["kv"][name].shape
        filled = jax.random.normal(next(keys), s[:4] + (cache_len,) + s[5:], cfg_model.cache_dtype)
        pad = [(0, 0)] * 4 + [(0, s_max - cache_len), (0, 0)]
        kv[name] = jnp.pad(filled, pad)
    k_conv, k_s = jax.random.split(k_st)
    caches = {
        "kv": kv,
        "ssm_conv": jax.random.normal(k_conv, shapes["ssm_conv"].shape, shapes["ssm_conv"].dtype),
        "ssm_state": jax.random.normal(k_s, shapes["ssm_state"].shape, shapes["ssm_state"].dtype),
    }
    tokens = jax.random.randint(k_tok, (batch, 1), 0, cfg_model.vocab_size, jnp.int32)
    return params, caches, tokens


# steps after the window checked against the reference decode step
CHECK_STEPS = 8


class Entry:
    CALL_SPAN = "bench.step"
    # DispatchStats fields this path fills (the pipeline dispatch)
    FILLED = ("stage_s", "transfer_s", "compile_s", "compute_s", "lowerings")

    def __init__(self, cfg: Dict, wl: Dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.core import ClassMapPolicy, CXLMemSim, EpochSchedule, spans
        from repro.models import Model
        from repro.models.phases import decode_program as program_of

        self.cfg, self.wl = cfg, wl
        mc = model_config(cfg)  # a program without the family fails here, at once
        sim_cfg, t = cfg["simulator"], cfg["tenant"]
        self.mix = traffic(wl, seed)
        batch, self.s_max = self.mix["batch"], self.mix["max_cache_len"]
        model = Model(mc)

        key = jax.random.PRNGKey(0)
        for w in tenants.seed_words(seed):
            key = jax.random.fold_in(key, w)
        with harness.span("bench.init"):
            self.params, self.caches, self.token = jax.jit(
                lambda k: draw_state(model, mc, batch, self.s_max, self.mix["cache_len"], k,
                                     weight_dtype=getattr(jnp, cfg["precision"]["params"]))
            )(key)
        self.cache_len = jnp.int32(self.mix["cache_len"])
        # the weights the reference step after the window reads (the
        # logits control puts other weights in the model's place)
        self.reference_params = self.params

        def decode(params, caches, token, cache_len):
            logits, caches, counts = model.decode_step(
                params, caches, token, cache_len, expert_counts=True)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return logits.astype(jnp.float32), nxt, caches, counts, cache_len + 1

        with harness.span("bench.compile"):
            compiled = (
                jax.jit(decode, donate_argnums=(1,))
                .lower(self.params, self.caches, self.token, self.cache_len)
                .compile()
            )

        def user_step(params, caches, token, cache_len):
            with harness.span("bench.tenant_step"):
                return compiled(params, caches, token, cache_len)

        rmap, build = program_of(
            mc, batch, self.s_max, param_dtype_bytes=t["param_dtype_bytes"],
            kv_dtype_bytes=t["kv_dtype_bytes"], state_dtype_bytes=t["state_dtype_bytes"])
        self.steps: List[Tuple[int, np.ndarray]] = []

        def program(out):
            counts, next_len = np.asarray(out[3]), int(out[4])
            self.steps.append((next_len - 1, counts))
            return build(next_len - 1, counts)

        # every plane bucket a step can reach: k of the held experts routed
        # in every layer, k = 0 .. held
        shape = (mc.n_layers, mc.n_held_experts)
        warm = [build(self.mix["cache_len"],
                      np.broadcast_to(np.arange(shape[1]) < k, shape).astype(np.int32))
                for k in range(shape[1] + 1)]
        topo = adapt.topology(cfg["fabric"], 1)
        policy = ClassMapPolicy(cfg["placement"], granularity_bytes=sim_cfg["granularity_bytes"])
        self.sim = CXLMemSim(
            topo, policy, epoch=EpochSchedule(sim_cfg["epoch"]), n_windows=sim_cfg["n_windows"],
            max_events_per_access=sim_cfg["max_events_per_access"],
            pipeline=bool(sim_cfg["pipeline"]), warmup=bool(sim_cfg["warmup"]),
        )
        with harness.span("bench.attach"):
            self.prog = self.sim.attach(user_step, program, rmap, warm_programs=warm)
        self.window = Window(cfg, batch, self.s_max)
        self.hosts, self.qos_on = 1, False
        self.marks: List[int] = []
        self.logits_gap = self.counts_gap = float("inf")
        # a step's least bytes are affine in its cache length
        least = costs_decode.step_least_bytes(cfg, batch, self.mix["cache_len"])
        self.least = (least, costs_decode.step_least_bytes(
            cfg, batch, self.mix["cache_len"] + 1) - least)
        self.host_len = self.mix["cache_len"]
        self.count = spans.count
        for _ in range(int(wl.get("warm_calls", 2))):
            self.call()
        self.flush()

    def call(self) -> None:
        self.count(costs_decode.LEAST_BYTES, self.least[0] + self.least[1] * (
            self.host_len - self.mix["cache_len"]))
        self.logits, self.token, self.caches, self.counts, self.cache_len = self.prog.step(
            self.params, self.caches, self.token, self.cache_len)
        self.host_len += 1

    def flush(self) -> None:
        import jax

        self.prog.flush()
        jax.block_until_ready(self.token)

    def snapshot(self) -> Dict:
        rep = self.prog.report
        self.marks.append(len(self.steps))
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
            "steps_seen": len(self.steps),
        }

    def window_steps(self) -> List[Tuple[int, np.ndarray]]:
        """The (cache_len, counts) of every step of the window (between the
        window's two snapshots)."""
        lo, hi = self.marks[-2], self.marks[-1]
        return self.steps[lo:hi]

    @property
    def events_per_call(self) -> float:
        """The window's own mean: a step's events follow its routing."""
        steps = self.window_steps()
        return sum(self.window.events(c, k) for c, k in steps) / max(len(steps), 1)

    def expected(self, q=oracle.exact) -> Dict:
        """The window's steps re-priced by the f64 oracle."""
        return self.window.price(self.window_steps(), q=q)

    def readings(self, win, ref: Dict) -> Dict[str, float]:
        got = {k: np.asarray(win.snap1["report"][k], np.float64)
               - np.asarray(win.snap0["report"][k], np.float64) for k in win.snap0["report"]}
        out = compare.class_gaps(got, ref["breakdown"])
        folded = win.snap1["epochs"] - win.snap0["epochs"]
        out["epochs_gap"] = float(abs(folded - ref["epochs"]))
        out["logits_gap"] = self.logits_gap
        out["counts_gap"] = self.counts_gap
        return out

    def close(self) -> None:
        """``CHECK_STEPS`` more steps, each compared with the plain float32
        reference decode step on the same state: its logits, and the
        held-expert counts it returned and its program was built from; then
        the session and its state are released."""
        import jax

        if not self.steps:
            self.prog.close()
            return
        got, want, got_counts, want_counts, same = [], [], [], [], True
        for _ in range(CHECK_STEPS):
            cache_len = int(self.cache_len)
            w, wc = granite4h.decode_step(
                self.reference_params, self.caches, self.token[:, 0], cache_len,
                self.cfg, offset=self.cfg["expert_parallel"]["first_held_expert"])
            self.call()
            got.append(np.asarray(jax.device_get(self.logits), np.float64))
            want.append(np.asarray(w, np.float64))
            got_counts.append(np.asarray(jax.device_get(self.counts)))
            want_counts.append(np.asarray(wc))
            priced_len, priced = self.steps[-1]
            same &= priced_len == cache_len and np.array_equal(priced, got_counts[-1])
        self.logits_gap = logits_gap(np.stack(got), np.stack(want))
        self.counts_gap = (counts_gap(np.stack(got_counts), np.stack(want_counts)) if same
                           else float("inf"))
        self.prog.close()
        del self.params, self.reference_params, self.caches, self.token, self.logits


def logits_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The checked steps' logit error, relative: ||got - want|| / ||want||
    over all [steps, B, V] logits.  A bfloat16 step differs from the
    float32 one by ~2% at these widths, and now and then a near-tie in a
    router's top-10 flips in one sequence; taken over every sequence of
    every checked step such a flip moves the reading by a fraction of that,
    where the widest single sequence would double it."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def counts_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The checked steps' held-expert counts [steps, layers, held] against
    the reference's: sum |got - want| / sum want.  A router near-tie that
    flips one token's choice between two experts moves the sum by at most 2
    of the ~90 tokens a step routes to held experts at batch 8, and such
    flips come in clusters (a flip changes that token's later layers);
    summed over several steps the clusters average out.  Counts taken from
    the wrong experts, layer or step move it by about its whole."""
    if got.shape != want.shape:
        return float("inf")
    return float(np.abs(got.astype(np.int64) - want).sum() / max(int(want.sum()), 1))
