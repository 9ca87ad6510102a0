"""The logits control of a decode cell, read by the benchmark's own
comparison: the cell runs as ``bench/run.py`` runs it, at its own shapes
and cache buffer, but once set-up is done the model's weight matrices are
rounded to the precision below the configuration's (float8_e4m3fn under
bfloat16, bfloat16 under float32) and held again in the configuration's
dtype.  The reference step after the window still reads the
configuration's own weights, so ``correct`` has to come out false, by
``logits_gap``; the pricing gaps stay within their limits (each step is
priced from what the rounded model routed).

    python3 bench/control_decode.py --workload <cell> --seed <n> --seconds <s> --trace 0

Prints what ``bench/run.py`` prints; the last line is the result.  The
benchmark's own runs never run it.  ``install()`` puts the rounding in
place for a run started later in the same process.
"""

import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402
import numpy as np  # noqa: E402

BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def control_entry(mod) -> None:
    """Replace the entry module's ``Entry`` by one whose model runs on
    weights rounded to the precision below (``serving_params``' split of
    weight matrices), the reference's copy kept on the host."""
    base = mod.Entry

    class Entry(base):
        def __init__(self, cfg, wl, seed):
            import jax
            import jax.numpy as jnp

            super().__init__(cfg, wl, seed)
            low = getattr(jnp, BELOW[cfg["precision"]["params"]])
            dtypes = jax.tree.map(lambda a: a.dtype, self.params)
            self.reference_params = jax.device_get(self.params)
            # down and back up in two programs, so that the low-precision
            # copy exists: done in one, the weights came back unrounded on
            # a v5e (the control read as the program)
            lowered = jax.jit(lambda p: mod.serving_params(p, low, jnp.float32))(self.params)
            del self.params  # the configuration's weights leave the chip
            self.params = jax.block_until_ready(jax.jit(
                lambda lo: jax.tree.map(lambda a, dt: a.astype(dt), lo, dtypes))(lowered))
            was, now = self.reference_params["embed"], np.asarray(self.params["embed"])
            print(f"control: {np.mean(was != now):.1%} of the embedding's entries rounded "
                  f"to {low.__name__}", file=sys.stderr, flush=True)

    mod.Entry = Entry


def install() -> None:
    load = harness.entry_module

    def entry_module(kind):
        mod = load(kind)
        if kind == "attach_decode":
            control_entry(mod)
        return mod

    harness.entry_module = entry_module


def main(argv=None) -> int:
    install()
    import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
