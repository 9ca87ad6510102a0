"""Sharding rules: logical tensor classes -> mesh PartitionSpecs.

Mesh axes: ``('data', 'model')`` single-pod, ``('pod', 'data', 'model')``
multi-pod.  Strategies:

  * ``'dp_tp'``   (baseline)  — batch on (pod, data); TP on model: attention
    heads / FFN hidden / vocab sharded; params otherwise replicated across
    data.  This is the classic Megatron layout.
  * ``'fsdp_tp'`` (ZeRO-3-style) — additionally shards every weight's
    *input* dim across 'data'; XLA inserts all-gathers at use and
    reduce-scatters of grads.  Required for ≥50B archs to fit HBM.

Every rule checks divisibility: a dim that does not divide its mesh axis is
left unsharded (e.g. granite's vocab 49155, hubert's vocab 504) — recorded
in EXPERIMENTS.md §Dry-run notes.  Expert dims: E on 'model' (EP) when
divisible, else the expert hidden dim.
"""

from __future__ import annotations

import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "batch_axes",
    "param_pspecs",
    "opt_pspecs",
    "input_pspecs",
    "named",
    "tree_named",
    "resolve_data_mesh",
    "pad_to_multiple",
    "shard_rows",
    "replicated",
    "timed_device_put",
]


# --------------------------------------------------------------------------- #
# Leading-axis ("data") dispatch sharding — used by the analyzer's stacked
# [K, B, N] dispatches (ScenarioSuite sweeps, AnalysisEngine coalescing,
# FleetSim racks).  The contract: the K leading axis shards over the mesh's
# 'data' axis; everything else (topology structure, skeleton stacks, unique
# cascades) replicates.
# --------------------------------------------------------------------------- #


def resolve_data_mesh(mesh: Optional[Mesh], rows: int, *, what: str = "dispatch"):
    """Validate ``mesh`` for sharding ``rows`` leading-axis rows.

    Returns ``(mesh, n_shards)``.  ``(None, 1)`` means sharding does not
    engage (no mesh, a single device, or nothing to shard).  When the mesh
    holds more devices along 'data' than there are rows, we fall back to a
    submesh over the first ``rows`` devices with a warning instead of letting
    XLA die on a shape-divisibility error — the work still runs, just on
    fewer shards.
    """
    if mesh is None or rows <= 0:
        return None, 1
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"sharded {what} needs a mesh with a 'data' axis; got axes "
            f"{tuple(mesh.axis_names)} — build one with "
            "repro.launch.mesh.make_data_mesh()"
        )
    n = int(mesh.shape["data"])
    for ax in mesh.axis_names:
        if ax != "data" and int(mesh.shape[ax]) != 1:
            raise ValueError(
                f"sharded {what} shards only the 'data' axis; mesh axis "
                f"{ax!r} has size {mesh.shape[ax]} > 1 (leading-axis rows "
                "cannot also shard over it)"
            )
    if n <= 1:
        return None, 1
    if n > rows:
        warnings.warn(
            f"mesh has {n} devices on 'data' but the {what} has only "
            f"{rows} rows; falling back to {rows} shard(s)",
            stacklevel=3,
        )
        devs = np.asarray(mesh.devices).reshape(-1)[:rows]
        sub = Mesh(devs, ("data",))
        return (None, 1) if rows == 1 else (sub, rows)
    return mesh, n


def pad_to_multiple(n: int, k: int) -> int:
    """Smallest multiple of ``k`` that is >= ``n`` (k <= 1 => n)."""
    if k <= 1:
        return n
    return ((n + k - 1) // k) * k


def shard_rows(mesh: Optional[Mesh], x):
    """Device_put ``x`` with its leading axis sharded over 'data'.

    No-op passthrough when ``mesh`` is None so callers can write one code
    path; the leading dim must be a multiple of the data-axis size (callers
    pad with :func:`pad_to_multiple` first).
    """
    if mesh is None:
        return x
    spec = P(*(("data",) + (None,) * (np.ndim(x) - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def replicated(mesh: Optional[Mesh], x):
    """Device_put ``x`` fully replicated over ``mesh`` (passthrough if None)."""
    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, P()))


def timed_device_put(tree, mesh: Optional[Mesh] = None, spec: Optional[P] = None):
    """H2D placement of a dispatch's staged planes; returns the device tree.

    The dispatch times this call under the ``cxlsim.h2d`` span
    (:mod:`repro.core.spans`) and reports it as ``transfer_s``.  Because
    the placement is an explicit ``device_put`` (not an implicit transfer
    inside the jitted call), the resulting device buffers are what
    ``donate_argnums`` consumes — donation engages on the copies, never on
    the caller's host staging planes.  With ``mesh`` (and optionally
    ``spec``) the placement is sharded; default is the single default
    device.
    """
    if mesh is None:
        return jax.device_put(tree)
    return jax.device_put(tree, NamedSharding(mesh, spec if spec is not None else P()))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _ok(mesh: Mesh, dim: int, axis) -> Optional[Any]:
    """Return axis if dim divides its mesh extent, else None."""
    return axis if axis is not None and dim % _axis_size(mesh, axis) == 0 else None


def _path_names(path) -> Tuple[str, ...]:
    return tuple(
        str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))) for k in path
    )


def param_pspecs(param_shapes, cfg, mesh: Mesh, strategy: str = "dp_tp"):
    """PartitionSpec pytree matching ``param_shapes`` (shapes or arrays).

    Strategies: 'dp_tp', 'fsdp_tp', plus '+moe_dp' suffix (e.g.
    'fsdp_tp+moe_dp') to replicate expert weights over the model axis —
    trades redundant expert compute for the elimination of the per-layer
    partial-sum all-reduce when E doesn't divide the model axis.
    """
    moe_dp = "+moe_dp" in strategy
    gqa_fix = "+gqa_fix" in strategy
    ep_data = "+ep_data" in strategy
    strategy = (
        strategy.replace("+moe_dp", "").replace("+gqa_fix", "").replace("+ep_data", "")
    )
    fsdp = "data" if strategy == "fsdp_tp" else None
    model = "model"
    msize = _axis_size(mesh, model)

    def rule(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        last = names[-1]
        in_blocks = "blocks" in names
        # scan-stacked params carry a leading n_groups dim; unrolled stacks
        # (scan_layers=False) are lists of groups — SequenceKey in the path
        unrolled = any(
            isinstance(k, jax.tree_util.SequenceKey) for k in path
        )
        lead = (None,) if (in_blocks and not unrolled) else ()

        def spec(*axes):
            axes = lead + axes
            # pad with None to rank
            axes = axes + (None,) * (len(shape) - len(axes))
            checked = tuple(
                _ok(mesh, shape[i], a) for i, a in enumerate(axes)
            )
            return P(*checked)

        if last == "embed":
            v, d = shape
            if v % _axis_size(mesh, model) == 0:
                return P(model, _ok(mesh, d, fsdp))
            return P(None, _ok(mesh, d, model))  # fallback: shard d_model
        if last == "lm_head":
            return spec(fsdp, model)
        if "attn" in names:
            # +gqa_fix: GSPMD cannot propagate a model-axis sharding through
            # the [.., Hk·Dh] -> [.., Hk, Dh] head split unless the HEAD count
            # divides the axis.  Sharding the flat projection anyway forces a
            # per-layer activation re-shard (measured: TB-scale all-reduce).
            # Fix: only shard projections whose head count divides the axis;
            # small KV projections are replicated instead.
            if gqa_fix:
                q_ok = cfg.n_heads % msize == 0
                kv_ok = cfg.n_kv_heads % msize == 0
                if last == "wq":
                    return spec(fsdp, model) if q_ok else spec(fsdp, None)
                if last in ("wk", "wv"):
                    return spec(fsdp, model) if kv_ok else spec(fsdp, None)
                if last == "wo":
                    return spec(model, fsdp) if q_ok else spec(None, fsdp)
                return spec()
            if last in ("wq", "wk", "wv"):
                return spec(fsdp, model)
            if last == "wo":
                return spec(model, fsdp)
            return spec()  # q_norm / k_norm
        if "moe" in names:
            E = cfg.n_held_experts
            ep_ok = E % _axis_size(mesh, model) == 0 and not moe_dp
            if last == "router":
                return spec(fsdp, None)
            if last in ("wi", "wu"):
                if ep_data:
                    return spec("data", None, model)  # EP on data, TP on hidden
                if moe_dp:
                    return spec(None, fsdp, None)  # experts replicated on model
                return spec(model, fsdp, None) if ep_ok else spec(None, fsdp, model)
            if last == "wo":
                if ep_data:
                    return spec("data", model, None)
                if moe_dp:
                    return spec(None, None, fsdp)
                return spec(model, None, fsdp) if ep_ok else spec(None, model, fsdp)
            if last in ("shared_wi", "shared_wu"):
                return spec(fsdp, model)
            if last == "shared_wo":
                return spec(model, fsdp)
            return spec()
        if "mlp" in names:
            if last in ("wi", "wu"):
                return spec(fsdp, model)
            if last == "wo":
                return spec(model, fsdp)
            return spec()
        if "mamba" in names:
            if last == "in_proj":
                return spec(fsdp, model)
            if last == "out_proj":
                return spec(model, fsdp)
            if last == "conv_w":
                return spec(None, model)
            if last == "conv_b":
                return spec(model)  # conv-width bias, sharded with conv_w
            if last == "norm":
                return spec(model)  # inner-width gain, sharded with di
            return spec()  # A_log, dt_bias, D
        return spec()  # norms etc.

    flat = jax.tree_util.tree_flatten_with_path(param_shapes)
    specs = [rule(p, l) for p, l in flat[0]]
    return jax.tree_util.tree_unflatten(flat[1], specs)


def opt_pspecs(param_specs, strategy: str = "dp_tp"):
    """Optimizer state specs: moments mirror the params; step replicated.

    Under plain dp_tp the moments additionally get ZeRO-1 treatment only if
    strategy requests it upstream — here they simply mirror the param spec
    (correct in both modes; fsdp_tp already shards the underlying params).
    """
    return {
        "mu": param_specs,
        "nu": param_specs,
        "step": P(),
    }


def input_pspecs(specs: Dict[str, Any], mesh: Mesh):
    """Sharding for step inputs (train batch or serve state)."""
    b_axes = batch_axes(mesh)
    baxis = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)

    def rule(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        first = names[0] if names else ""
        if first in ("tokens", "labels", "embeds", "token", "embed"):
            b = _ok(mesh, shape[0], baxis)
            return P(b, *([None] * (len(shape) - 1)))
        if first == "caches":
            last = names[-1]
            if last in ("k", "v"):
                # [G, na, B, Hk, Smax, Dh]: batch + sequence sharding
                g_, na_, B, Hk, S, Dh = shape
                b = _ok(mesh, B, baxis)
                s = _ok(mesh, S, "model")
                return P(None, None, b, None, s, None)
            if last == "ssm_conv":
                g_, nm_, B, k_, di = shape
                return P(None, None, _ok(mesh, B, baxis), None, _ok(mesh, di, "model"))
            if last == "ssm_state":
                g_, nm_, B, H, N, Pd = shape
                return P(
                    None, None, _ok(mesh, B, baxis), _ok(mesh, H, "model"), None, None
                )
        if first == "cache_len":
            return P()
        return P(*([None] * len(shape)))

    flat = jax.tree_util.tree_flatten_with_path(specs)
    out = [rule(p, l) for p, l in flat[0]]
    return jax.tree_util.tree_unflatten(flat[1], out)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
