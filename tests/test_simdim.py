"""simdim end-to-end: the units and axes abstract interpreters on the
seeded-violation corpus, the runtime AxisSanitizer against transposed
dispatches (including a real ``[K, B, N]`` analyzer surface), and the
bitwise-neutrality guarantees of the annotation layer and the
``repro.core.units`` helpers.
"""
import dataclasses
import inspect
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import registered_checkers, run_checks
from repro.analysis.framework import CheckConfig
from repro.analysis.annotations import (
    AxisContractError,
    axes,
    axes_validation,
    unit,
)
from repro.analysis.sanitize import AxisSanitizer
from repro.core import units as U

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "simlint"


# the axes corpus names its own dispatch surface (a standalone stand-in,
# not one of the repo's): the fixtures check the rule, not the repo's list
FIXTURE_CONFIG = dataclasses.replace(
    CheckConfig(),
    axes_required=CheckConfig().axes_required + ("_analyze_multi_jax",),
)


def _check(*names, checkers=None, strict=False):
    return run_checks(
        [FIXTURES / n for n in names],
        root=FIXTURES,
        strict=strict,
        checker_names=checkers,
        config=FIXTURE_CONFIG,
    )


def _rules(rep):
    out = {}
    for f in rep.findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return out


# --------------------------------------------------------------------------- #
# units checker: seeded corpus
# --------------------------------------------------------------------------- #


def test_units_checker_is_registered():
    assert "units" in registered_checkers()
    assert "axes" in registered_checkers()


def test_units_corpus_all_rules_fire():
    rep = _check("bad_units.py", checkers=["units"])
    assert _rules(rep) == {
        "unit-mismatch": 4,
        "unit-return": 1,
        "unit-raw-conversion": 1,
    }, [f.format() for f in rep.findings]


def test_units_cross_unit_add_names_both_units():
    rep = _check("bad_units.py", checkers=["units"])
    msgs = [f.message for f in rep.findings]
    assert "mixing ns with s" in msgs
    assert "comparison of ns against s" in msgs
    assert any("expects a ns input, got s" in m for m in msgs)


def test_units_clean_counterpart_has_no_findings():
    rep = _check("good_units.py", checkers=["units"])
    assert rep.ok, [f.format() for f in rep.findings]


def test_units_bandwidth_identity_needs_no_annotation():
    # good_units.py relies on GB/s == bytes/ns: wbytes / bw_gbps is already
    # nanoseconds and must NOT be flagged as a cross-unit operation.
    rep = _check("good_units.py", checkers=["units"])
    assert not any("gbps" in f.message for f in rep.findings)


# --------------------------------------------------------------------------- #
# axes checker: seeded corpus
# --------------------------------------------------------------------------- #


def test_axes_corpus_all_rules_fire():
    rep = _check("bad_axes.py", checkers=["axes"])
    assert _rules(rep) == {
        "axes-missing": 1,
        "axes-mismatch": 3,
        "axes-rank": 2,
    }, [f.format() for f in rep.findings]


def test_axes_missing_names_the_surface():
    rep = _check("bad_axes.py", checkers=["axes"])
    missing = [f for f in rep.findings if f.rule == "axes-missing"]
    assert len(missing) == 1
    assert "_analyze_multi_jax" in missing[0].message


def test_axes_transposed_dispatch_is_flagged():
    rep = _check("bad_axes.py", checkers=["axes"])
    mism = [f.message for f in rep.findings if f.rule == "axes-mismatch"]
    assert any("transposed" in m for m in mism), mism


def test_axes_clean_counterpart_has_no_findings():
    # Consistent renaming (G for K), transpose round-trips, vmap closures
    # and keepdims reductions must all stay quiet.
    rep = _check("good_axes.py", checkers=["axes"])
    assert rep.ok, [f.format() for f in rep.findings]


def test_axes_required_surfaces_all_annotated_in_repo():
    # The acceptance criterion: every listed jitted entry point carries a
    # contract, so the repo-wide axes pass emits no axes-missing.
    rep = run_checks(
        [REPO / "src" / "repro"], root=REPO, checker_names=["axes"],
    )
    assert not [f for f in rep.findings if f.rule == "axes-missing"], [
        f.format() for f in rep.findings
    ]


# --------------------------------------------------------------------------- #
# annotation layer: validation and transparency
# --------------------------------------------------------------------------- #


def test_unit_marker_is_identity():
    x = jnp.arange(4.0)
    assert unit("ns", x) is x
    with pytest.raises(ValueError):
        unit("", x)


def test_axes_decorator_rejects_bad_specs():
    with pytest.raises(ValueError):
        axes("K,B!,N")(lambda t: t)
    with pytest.raises(ValueError):
        axes(nosuch="K,N")(lambda t: t)
    with pytest.raises(ValueError):
        axes("K", "B", "N")(lambda t: t)  # more specs than params


def test_axes_wrapper_is_signature_transparent():
    @axes("K,B,N", stts="S")
    def f(t, stts, n_hosts=1):
        return t.sum() + stts.sum()

    assert f.__wrapped__ is not None
    assert list(inspect.signature(f).parameters) == ["t", "stts", "n_hosts"]
    assert f.__simlint_axes__["t"] == ("K", "B", "N")


def test_axes_wrapper_bitwise_identity():
    @axes("K,B,N", stts="S")
    def f(t, stts):
        return t * stts.sum() + jnp.float32(1.5)

    t = jnp.asarray(np.random.default_rng(0).random((2, 3, 4)), jnp.float32)
    stts = jnp.arange(5, dtype=jnp.float32)
    with AxisSanitizer():
        armed = f(t, stts)
    off = f(t, stts)
    raw = f.__wrapped__(t, stts)
    np.testing.assert_array_equal(np.asarray(armed), np.asarray(raw))
    np.testing.assert_array_equal(np.asarray(off), np.asarray(raw))


# --------------------------------------------------------------------------- #
# runtime AxisSanitizer
# --------------------------------------------------------------------------- #


@axes("K,B,N", bw="K,B", stts="S")
def _toy_dispatch(t, bw, stts, n_hosts=1):
    # rank-agnostic body: runs (wrongly) even on a transposed plane, so the
    # sanitizer is the only thing standing between the bug and a result
    return t.sum(axis=-1) + bw.sum() * 0 + stts.sum() * 0


def _toy_args(transpose_t=False):
    K, B, N, S = 2, 3, 4, 5
    t = jnp.ones((K, B, N), jnp.float32)
    if transpose_t:
        t = jnp.transpose(t, (1, 0, 2))  # [B, K, N]: the seeded violation
    return t, jnp.ones((K, B), jnp.float32), jnp.ones((S,), jnp.float32)


def test_sanitizer_passes_valid_shapes():
    with AxisSanitizer():
        out = _toy_dispatch(*_toy_args())
    assert out.shape == (2, 3)


@pytest.mark.no_sanitize  # asserts the wrapper is inert outside any scope
def test_sanitizer_detects_transposed_dispatch():
    t, bw, stts = _toy_args(transpose_t=True)
    with AxisSanitizer():
        with pytest.raises(AxisContractError, match="axis"):
            _toy_dispatch(t, bw, stts)
    # off-scope: the wrapper is a pure pass-through, no validation
    assert _toy_dispatch(t, bw, stts).shape == (3, 2)


def test_sanitizer_detects_transposition_at_jit_trace_time():
    jf = jax.jit(_toy_dispatch, static_argnames=("n_hosts",))
    t, bw, stts = _toy_args(transpose_t=True)
    with AxisSanitizer():
        with pytest.raises(AxisContractError):
            jf(t, bw, stts, n_hosts=2)


def test_sanitizer_record_only_collects_instead_of_raising():
    t, bw, stts = _toy_args(transpose_t=True)
    with AxisSanitizer(record_only=True) as san:
        out = _toy_dispatch(t, bw, stts)
    assert out.shape == (3, 2)
    # record mode keeps validating past the first failure: both the K and
    # the B binding of the transposed plane are reported
    assert len(san.violations) >= 1
    assert all("_toy_dispatch" in v for v in san.violations)


def test_sanitizer_innermost_scope_wins():
    t, bw, stts = _toy_args(transpose_t=True)
    with axes_validation():  # raising outer scope (the autouse harness)
        with AxisSanitizer(record_only=True) as san:
            _toy_dispatch(t, bw, stts)
        assert san.violations
        with pytest.raises(AxisContractError):
            _toy_dispatch(t, bw, stts)


def test_sanitizer_detects_transposed_real_analyzer_dispatch():
    """The acceptance scenario: a [K, B, N] plane fed as [B, K, N] into the
    real stacked surface trips the contract before any compute."""
    from repro.core.analyzer import _analyze_stack_jax

    K, B, N, V, S, C = 2, 3, 4, 2, 2, 1
    f32 = jnp.float32
    i32 = jnp.int32
    plane = lambda dt: jnp.zeros((K, B, N), dt)  # noqa: E731
    kwargs = dict(
        t=jnp.transpose(jnp.ones((K, B, N), f32), (1, 0, 2)),  # [B, K, N]
        pool=plane(i32),
        nbytes=jnp.ones((K, B, N), f32),
        weight=jnp.ones((K, B, N), f32),
        host=plane(i32),
        qos=plane(i32),
        valid=jnp.ones((K, B, N), bool),
        bw_window_ns=jnp.full((K, B), 1e6, f32),
        lat_scale=jnp.ones((K, B, V), f32),
        bits_table=jnp.zeros((V,), i32),
        pool_latency_ns=jnp.ones((K, V), f32),
        local_latency_ns=jnp.full((K,), 100.0, f32),
        route=jnp.zeros((V, S), f32),
        switch_stt_ns=jnp.ones((K, S), f32),
        switch_bw=jnp.ones((K, S), f32),
        disc_code=jnp.zeros((K, S), i32),
        class_weights=jnp.ones((K, S, C), f32),
        stage_order=(0, 1),
        n_windows=1,
        n_hosts=1,
    )
    with AxisSanitizer(record_only=True) as san:
        try:
            _analyze_stack_jax(**kwargs)
        except Exception:
            pass  # downstream shape errors are expected; the record matters
    assert san.violations, "transposed [B,K,N] dispatch went undetected"
    assert "_analyze_stack_jax" in san.violations[0]


# --------------------------------------------------------------------------- #
# units helpers: bitwise neutrality of the centralization satellite
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("x", [0.0, 1.0, 137.25, 3.333e7, 1e-3])
def test_units_helpers_match_raw_literal_arithmetic(x):
    # Each helper keeps the exact arithmetic form of the literal it replaced,
    # so every converted call site is bitwise-identical to the seed.
    assert U.ns_to_s(x) == x * 1e-9
    assert U.s_to_ns(x) == x * 1e9
    assert U.s_to_ms(x) == x * 1e3
    assert U.ns_to_ms(x) == x / 1e6
    assert U.ms_to_ns(x) == x * 1e6
    assert U.ns_to_us(x) == x / 1e3
    assert U.us_to_ns(x) == x * 1e3
    assert U.bytes_to_mib(x) == x / 2**20
    assert U.mib_to_bytes(x) == x * 2**20
    assert U.bytes_to_gib(x) == x / 2**30
    assert U.gib_to_bytes(x) == x * 2**30


def test_units_constants_values():
    assert U.NS_PER_S == 1e9 and U.S_PER_NS == 1e-9
    assert U.NS_PER_MS == 1e6 and U.NS_PER_US == 1e3
    assert U.BYTES_PER_GIB == 2**30 and U.BYTES_PER_MIB == 2**20
    assert U.BYTES_PER_GB == 1e9 and U.MS_PER_S == 1e3
