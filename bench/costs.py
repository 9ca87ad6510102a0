"""The least work of the analyzer, counted from the events it priced.

To price an epoch the analyzer has to read, once, every real event's
time, pool, byte count and weight, and its host where the fabric has more
than one host and its class where switches arbitrate.  Each column is
4 bytes on the device (float32 / int32).  Its outputs are per-pool,
per-switch and per-host sums, a few hundred bytes per dispatch, and are
left out.  Padding slots are not work: a graph that reads them spends
bandwidth the count below does not grant.

The arithmetic is a few max-plus and add operations per event and switch
stage, so at 197 TFLOP/s against 819 GB/s the bytes bound the time: the
roofline is the bytes over peak bandwidth.
"""

from __future__ import annotations

COLUMN_BYTES = 4


def analyzer_columns(n_hosts: int, qos_on: bool) -> int:
    return 4 + (1 if n_hosts > 1 else 0) + (1 if qos_on else 0)


def analyzer_least_bytes(n_events: int, n_hosts: int, qos_on: bool) -> float:
    return float(n_events) * analyzer_columns(n_hosts, qos_on) * COLUMN_BYTES


def roofline_share(n_events: int, n_hosts: int, qos_on: bool, device_s: float,
                   peak_bytes_per_s: float) -> float:
    """Least time at peak bandwidth over the device time, in percent."""
    least_s = analyzer_least_bytes(n_events, n_hosts, qos_on) / peak_bytes_per_s
    return 100.0 * least_s / device_s
