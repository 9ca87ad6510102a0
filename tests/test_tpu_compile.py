"""Compile-only checks against a described TPU v5e: the kernels and dispatch
graphs of the simulator's main path, at real epoch sizes (N = 65,536).

Nothing runs here — XLA's TPU compiler refuses what the chip would refuse
(Mosaic lowering rules, tiling, VMEM limits), which interpret-mode tests
cannot show.  The topology is described inside a module fixture, and every
test skips when it cannot be described; keep these tests in this one file
so a single worker holds the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core import EpochAnalyzer, Topology, figure1_topology, pooled_topology
from repro.core.analyzer import _analyze_batch_jax, _analyze_pipeline_jax
from repro.core.events import plane_layout, plane_words
from repro.core.topology import chained_topology
from repro.kernels import ops
from repro.kernels.congestion import stage_scan
from repro.kernels.flash_attention import flash_attention

N = 65_536
B = 8
F32, I32 = jnp.float32, jnp.int32
_BATCH = jax.jit(
    _analyze_batch_jax,
    static_argnames=(
        "stage_order", "n_windows", "n_hosts", "impl", "fused", "merge_plan", "qos_on",
    ),
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args, **static):
    """Compile ``fn`` (a function or a jitted one) for the described chip
    and return the compiled module's text."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jitted.lower(*args, **static)
    return lowered.compile().as_text()


@pytest.mark.parametrize("n_classes,n_seg", [(1, 1), (1, 4), (3, 12)])
def test_stage_scan_compiles(sds, n_classes, n_seg):
    args = [sds((N,), F32), sds((N,), jnp.bool_), sds((n_classes,), F32)]
    kw = {}
    if n_classes > 1:
        args += [sds((N,), I32), sds((n_classes,), I32)]
    if n_seg > 1:
        args += [sds((N,), I32)]

    def fn(t, m, stt, *rest):
        rest = list(rest)
        if n_classes > 1:
            kw["q"], kw["lo"] = rest.pop(0), rest.pop(0)
        if n_seg > 1:
            kw["seg"] = rest.pop(0)
        return stage_scan(t, m, stt, n_seg=n_seg, **kw)

    assert "tpu_custom_call" in _compile(fn, *args)


def test_congestion_queue_compiles(sds):
    fn = functools.partial(ops.congestion_queue, impl="pallas")
    assert "tpu_custom_call" in _compile(
        fn, sds((N,), F32), sds((N,), jnp.bool_), sds((), F32)
    )


@pytest.mark.parametrize("variant", ["fifo", "hosts", "qos_hosts"])
def test_cascade_wrappers_compile(sds, variant):
    t, bits, hosts = sds((N,), F32), sds((N,), I32), sds((N,), I32)
    if variant == "fifo":
        fn = functools.partial(ops.congestion_cascade, impl="pallas")
        text = _compile(fn, t, bits, sds((3,), F32))
    elif variant == "hosts":
        fn = functools.partial(ops.congestion_cascade, impl="pallas", n_hosts=4)
        text = _compile(
            lambda a, b, s, h: fn(a, b, s, hosts=h), t, bits, sds((4,), F32), hosts
        )
    else:
        def fn(a, b, s, q, d, w, h):
            return ops.qos_congestion_cascade(
                a, b, s, q, d, w, impl="pallas", hosts=h, n_hosts=4
            )

        text = _compile(
            fn, t, bits, sds((3,), F32), sds((N,), I32), sds((3,), I32),
            sds((3, 3), F32), hosts,
        )
    assert "tpu_custom_call" in text


def _leaves(an, sds):
    leaves = (an._pool_lat, an._local_lat, an._route, an._stt, an._bw)
    return [sds(a.shape, a.dtype) for a in leaves]


def test_pipeline_chain_dispatch_compiles(sds):
    """The device-resident pipeline's depth-8 chain graph at B = 8."""
    flat = chained_topology(8).flatten()
    an = EpochAnalyzer(flat, pipeline=True)
    V = flat.route.shape[0]
    order = an._chain_plan.stage_order  # the RC is a stage too
    caps = (N // len(order),) * (len(order) - 1)
    caps += (N - sum(caps),)
    layout = plane_layout(B, N, V, sum(caps))
    args = [sds((plane_words(layout),), I32)] + _leaves(an, sds)  # donated plane
    jitted = jax.jit(
        _analyze_pipeline_jax,
        static_argnames=("layout", "stage_order", "seg_caps", "n_windows"),
        donate_argnums=(0,),
    )
    _compile(
        jitted, *args, layout=layout, stage_order=order, seg_caps=caps,
        n_windows=an.n_windows,
    )


@pytest.mark.parametrize("impl", ["inline", "pallas"])
def test_batch_dispatch_figure1_4hosts_compiles(sds, impl):
    f1 = figure1_topology()
    flat = Topology(
        f1.pools, f1.switches, rc_latency_ns=f1.rc_latency_ns,
        rc_bandwidth_gbps=f1.rc_bandwidth_gbps, rc_stt_ns=f1.rc_stt_ns, n_hosts=4,
    ).flatten()
    an = EpochAnalyzer(flat, impl=impl)
    V = flat.route.shape[0]
    plane = [sds((B, N), t) for t in (F32, I32, F32, F32, I32, I32, jnp.bool_)]
    args = plane + [sds((B,), F32), sds((B, V), F32), sds((V,), I32)]
    args += _leaves(an, sds) + [sds(an._disc.shape, I32), sds(an._weights.shape, F32)]
    _compile(
        _BATCH, *args, stage_order=an._stage_order, n_windows=an.n_windows,
        n_hosts=flat.n_hosts, impl=impl, merge_plan=an._merge_plan,
    )


def test_batch_dispatch_qos_fabric_pallas_compiles(sds):
    flat = pooled_topology(
        n_hosts=4, discipline="wfq", class_weights=(4.0, 2.0, 1.0)
    ).flatten()
    an = EpochAnalyzer(flat, impl="pallas")
    assert an.qos_on
    V = flat.route.shape[0]
    plane = [sds((B, N), t) for t in (F32, I32, F32, F32, I32, I32, jnp.bool_)]
    args = plane + [sds((B,), F32), sds((B, V), F32), sds((V,), I32)]
    args += _leaves(an, sds) + [sds(an._disc.shape, I32), sds(an._weights.shape, F32)]
    _compile(
        _BATCH, *args, stage_order=an._stage_order, n_windows=an.n_windows,
        n_hosts=flat.n_hosts, impl="pallas", merge_plan=an._merge_plan, qos_on=True,
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles(sds, dtype):
    q = sds((1, 16, 1024, 128), dtype)
    kv = sds((1, 8, 1024, 128), dtype)
    assert "tpu_custom_call" in _compile(flash_attention, q, kv, kv)
