"""The readers of the program's own spans and counters, against a stubbed
table: nothing to read gives None, and the arithmetic per event and per
slot is checked by hand."""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import program_spans  # noqa: E402

# one serve window: 1,000 rounds of 30,976 events on [32, 1024] planes
EVENTS = 30_976_000
TABLE = {
    "cxlsim.enqueue": (1000, 0.31),
    "cxlsim.wait": (1000, 2.2),
    "cxlsim.d2h": (1000, 0.25),
    "cxlsim.fold": (1000, 0.12),
    "cxlsim.slots": (32_768_000, 0.0),
    "cxlsim.events": (30_976_000, 0.0),
}
SPAN_READERS = {
    "enqueue_ns_per_event": "cxlsim.enqueue",
    "wait_ns_per_event": "cxlsim.wait",
    "d2h_ns_per_event": "cxlsim.d2h",
    "fold_ns_per_event": "cxlsim.fold",
    "submit_wait_ns_per_event": "cxlsim.submit_wait",
}
METRICS = list(SPAN_READERS) + ["slot_fill", "window_compiles"]


def stub(monkeypatch, table):
    monkeypatch.setattr(program_spans, "totals", lambda: table or None)


def read(metric, events=EVENTS):
    return harness.reader(metric + ".events").read(SimpleNamespace(events=events))


@pytest.mark.parametrize("metric", METRICS)
def test_empty_table_gives_none(monkeypatch, metric):
    stub(monkeypatch, {})
    assert read(metric) is None


@pytest.mark.parametrize("metric", METRICS)
def test_program_without_spans_gives_none(monkeypatch, metric):
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)  # import fails
    assert read(metric) is None


@pytest.mark.parametrize("metric", list(SPAN_READERS))
def test_span_seconds_per_event(monkeypatch, metric):
    stub(monkeypatch, TABLE)
    _, seconds = TABLE.get(SPAN_READERS[metric], (0, 0.0))
    assert read(metric) == pytest.approx(seconds / EVENTS * 1e9, rel=1e-12)
    assert read(metric, events=0) is None


def test_wait_reads_its_own_span(monkeypatch):
    stub(monkeypatch, TABLE)
    # 2.2 s over 30.976 M events
    assert read("wait_ns_per_event") == pytest.approx(71.0227, rel=1e-5)
    assert read("submit_wait_ns_per_event") == 0.0  # no backpressure met


def test_slot_fill_is_events_over_slots(monkeypatch):
    stub(monkeypatch, TABLE)
    assert read("slot_fill") == pytest.approx(94.53125, rel=1e-12)
    stub(monkeypatch, {"cxlsim.enqueue": (3, 0.1)})  # no dispatch counted
    assert read("slot_fill") is None


def test_window_compiles_counts_backend_compiles(monkeypatch):
    stub(monkeypatch, TABLE)
    assert read("window_compiles") == 0.0
    stub(monkeypatch, dict(TABLE, **{"cxlsim.compile.backend": (2, 1.5)}))
    assert read("window_compiles") == 2.0


def test_totals_reads_the_program_table():
    from repro.core import spans

    spans.reset()
    assert program_spans.totals() is None
    with spans.span("cxlsim.fold"):
        pass
    assert program_spans.totals() is None  # no session recorded it
