"""Production mesh definition (see brief: MULTI-POD DRY-RUN step 1)."""

from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "make_data_mesh",
]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    A FUNCTION (not module-level state) so importing this module never
    touches jax device state; callers control XLA_FLAGS first.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(min(model, n // data), 1)
    return jax.make_mesh((data, model), ("data", "model"))


def make_data_mesh(n: int | None = None):
    """1-D ``('data',)`` mesh over the first ``n`` (default: all) devices.

    The mesh shape the analyzer's sharded dispatch expects: stacked
    ``[K, B, N]`` dispatches shard their leading scenario/session/rack axis
    over 'data' (see ``repro.distributed.sharding.resolve_data_mesh``).
    Built with ``jax.sharding.Mesh`` directly so a subset of devices works.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs) if n is None else max(1, min(int(n), len(devs)))
    return Mesh(np.array(devs[:n]), ("data",))
