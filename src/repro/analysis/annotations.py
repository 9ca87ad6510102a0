"""Source-level annotations the simlint checkers understand.

These are ordinary runtime objects (introspectable, importable with zero
dependencies on the analysis framework) whose *syntactic* form is what the
AST checkers read — annotating a class never changes its behavior.
"""

from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, Tuple, TypeVar

__all__ = [
    "AxisContractError",
    "axes",
    "axes_validation",
    "guarded_by",
    "single_threaded",
    "unit",
]

F = TypeVar("F", bound=Callable)


def guarded_by(lock: str, *fields: str) -> Dict[str, Tuple[str, ...]]:
    """Declare that ``fields`` may only be accessed while ``<lock>`` is held.

    Used as a class-body declaration::

        class Session:
            _simlint_guards = guarded_by("_report_lock", "_report")

    Each field is an attribute name (``"_report"`` matches any
    ``<expr>._report``) or a dotted pair (``"_handle.dropped_batches"``
    matches only ``<expr>._handle.dropped_batches``), so fields of owned
    sub-objects can be guarded without claiming every same-named attribute.
    ``lock`` is matched by the *final* attribute name of a with-item's
    context expression: ``with self._cv:``, ``with eng._cv:`` and
    ``with self.engine._cv:`` all hold ``"_cv"`` — the convention is that a
    lock attribute name identifies one lock protocol wherever it appears.

    The lock-discipline checker exempts ``__init__``/``__post_init__``
    (single-threaded by construction), methods whose name ends in
    ``_locked`` (the caller-holds-the-lock convention this repo already
    uses), and methods decorated with :func:`single_threaded`.

    Declarations merge with ``|``::

        _simlint_guards = guarded_by("_cv", "_pending") | guarded_by(...)
    """
    return {lock: tuple(fields)}


def single_threaded(reason: str) -> Callable[[F], F]:
    """Mark a method as running on one thread only (checker-exempt).

    The reason is mandatory — an unexplained exemption is how the next
    reader reintroduces the race::

        @single_threaded("dispatcher-thread only: stagers never escape it")
        def _stager_for(self, analyzer): ...
    """
    if not isinstance(reason, str) or not reason.strip():
        raise ValueError("single_threaded requires a non-empty reason string")

    def mark(fn: F) -> F:
        fn.__simlint_single_threaded__ = reason  # type: ignore[attr-defined]
        return fn

    return mark


# --------------------------------------------------------------------------- #
# units


def unit(u: str, x: Any) -> Any:
    """Assert the physical unit of ``x`` for the units checker; returns ``x``.

    An identity at runtime — the *string literal* is what the abstract
    interpreter reads, so it must be a literal at the call site::

        budget = unit("ns", window_end - window_start)

    Unit vocabulary matches the name-suffix seeds: ``"ns"``, ``"s"``,
    ``"ms"``, ``"us"``, ``"bytes"``, ``"gbps"`` (GB/s == bytes/ns),
    ``"gib"``, ``"mib"``, ``"1"`` (dimensionless).  Compound units use
    ``/``: ``"bytes/s"``.
    """
    if not isinstance(u, str) or not u.strip():
        raise ValueError("unit() requires a non-empty unit string literal")
    return x


# --------------------------------------------------------------------------- #
# named-axis shape contracts


class AxisContractError(TypeError):
    """An array reached an ``@axes``-annotated function with the wrong shape."""


_AXES_ACTIVE = 0  # nesting depth of active axes_validation() scopes
_AXES_SINK: Any = None  # innermost scope's record-only list, or None to raise


class axes_validation:
    """Context manager that arms runtime checking of ``@axes`` contracts.

    Zero-cost when not entered: decorated functions check one module-global
    integer and call straight through.  Used by
    :class:`repro.analysis.sanitize.AxisSanitizer`; nests correctly.

    With ``sink`` (a list), violation messages are appended to it instead
    of raising — the innermost scope's mode wins while it is active.
    """

    def __init__(self, sink: Any = None) -> None:
        self._sink = sink
        self._prev_sink: Any = None

    def __enter__(self) -> "axes_validation":
        global _AXES_ACTIVE, _AXES_SINK
        _AXES_ACTIVE += 1
        self._prev_sink = _AXES_SINK
        _AXES_SINK = self._sink
        return self

    def __exit__(self, *exc: Any) -> None:
        global _AXES_ACTIVE, _AXES_SINK
        _AXES_ACTIVE -= 1
        _AXES_SINK = self._prev_sink


def _parse_spec(spec: str) -> Tuple[str, ...]:
    toks = tuple(t.strip() for t in spec.split(",")) if spec.strip() else ()
    for t in toks:
        if not (t == "_" or t.isdigit() or t.isidentifier()):
            raise ValueError(f"bad axis token {t!r} in spec {spec!r}")
    return toks


def axes(*pos_specs: str, **kw_specs: str) -> Callable[[F], F]:
    """Declare named-axis shape contracts on a function's array parameters.

    Positional specs bind to the function's leading parameters in order;
    keyword specs bind by parameter name::

        @axes("K,B,N", stts="K,S", class_weights="S,C")
        def _analyze_stack_jax(xs, stts, route, ...): ...

    A spec is a comma-separated axis list.  Tokens are axis *names*
    (``K``, ``B``, ``N`` — unified across all parameters of one call, so a
    transposed ``[B,K,N]`` dispatch fails the moment ``K`` binds two
    different sizes), integer literals (exact size), or ``_`` (wildcard).
    The empty spec ``""`` means scalar (rank 0).

    The static axes checker (:mod:`repro.analysis.axes`) reads the
    decorator syntactically and propagates the contracts through
    ``vmap``/``transpose``/reductions; at runtime the wrapper is an
    identity unless an :class:`axes_validation` scope (armed by the
    ``SIMLINT_SANITIZE=1`` :class:`~repro.analysis.sanitize.AxisSanitizer`)
    is active — then every call validates declared axes against actual
    ``.shape`` tuples, **including at jit trace time**, since traced
    arguments carry concrete shapes.  Parameters bound to ``None`` or to
    shapeless values are skipped.  ``functools.wraps`` publishes
    ``__wrapped__``, so ``jax.jit(fn, static_argnames=...)`` and
    ``donate_argnums`` keep resolving signatures through the wrapper.
    """
    parsed_kw = {name: _parse_spec(s) for name, s in kw_specs.items()}
    parsed_pos = tuple(_parse_spec(s) for s in pos_specs)

    def deco(fn: F) -> F:
        sig = inspect.signature(fn)
        params = [
            p.name
            for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        ]
        if len(parsed_pos) > len(params):
            raise ValueError(
                f"axes(): {len(parsed_pos)} positional specs but "
                f"{fn.__name__} has only {len(params)} positional parameters"
            )
        specs: Dict[str, Tuple[str, ...]] = dict(zip(params, parsed_pos))
        for name, toks in parsed_kw.items():
            if name not in sig.parameters:
                raise ValueError(f"axes(): {fn.__name__} has no parameter {name!r}")
            specs[name] = toks

        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            if _AXES_ACTIVE:
                _validate(fn.__qualname__, sig, specs, args, kwargs)
            return fn(*args, **kwargs)

        wrapper.__simlint_axes__ = specs  # type: ignore[attr-defined]
        return wrapper  # type: ignore[return-value]

    return deco


def _fail(msg: str) -> None:
    if _AXES_SINK is not None:
        _AXES_SINK.append(msg)
        return
    raise AxisContractError(msg)


def _validate(
    qualname: str,
    sig: inspect.Signature,
    specs: Dict[str, Tuple[str, ...]],
    args: Tuple[Any, ...],
    kwargs: Dict[str, Any],
) -> None:
    try:
        bound = sig.bind(*args, **kwargs)
    except TypeError:
        return  # let the call itself raise the real signature error
    env: Dict[str, int] = {}
    for name, toks in specs.items():
        if name not in bound.arguments:
            continue
        val = bound.arguments[name]
        if val is None:
            continue
        shape = getattr(val, "shape", None)
        if shape is None:
            continue
        shape = tuple(shape)
        if len(shape) != len(toks):
            _fail(
                f"{qualname}: {name} declared axes [{','.join(toks)}] "
                f"(rank {len(toks)}) but got shape {shape} (rank {len(shape)})"
            )
            continue
        for i, (tok, dim) in enumerate(zip(toks, shape)):
            if tok == "_":
                continue
            if tok.isdigit():
                if int(tok) != dim:
                    _fail(
                        f"{qualname}: {name} axis {i} declared {tok} "
                        f"but got {dim} (shape {shape})"
                    )
                continue
            if tok in env and env[tok] != dim:
                _fail(
                    f"{qualname}: axis {tok!r} bound to {env[tok]} earlier in "
                    f"this call but {name} has {tok}={dim} at position {i} "
                    f"(shape {shape}) — transposed or mismatched dispatch"
                )
            env[tok] = dim
