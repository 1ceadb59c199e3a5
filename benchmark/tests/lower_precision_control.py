"""The control of a ``serve_lm`` cell's ``correct``: the cell run as ``run.py`` runs it,
except that the system SERVES its weights one precision lower (every leaf of the class's
serving type rounded through ``--round`` and back) while the configuration's reference
scores the served tokens over the weights as built.  ``check_served`` has to come out not
``ok``, by the limits it ships with: a comparison that a float8 deployment passes guards
nothing.

    python3 benchmark/tests/lower_precision_control.py --workload minicpm-sala.longctx \
        --seed 3500000301 --seconds 45 [--round float8_e4m3fn]

Prints ``run.py``'s line (``correct`` false; ``notes.check`` holds the margins beside
their limits).  Exit code 0 when the control held (every request came back whole and
the check is not ``ok``), 1 when the lowered system passed.  The unrounded weights wait
on the HOST while the system serves (two copies do not fit one chip beside the state);
the reference moves a layer at a time to the device, as it upcasts a layer at a time.
"""

from __future__ import annotations

import importlib
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def lowered(cls, round_to: str):
    """``cls`` whose ``build`` rounds every leaf of the serving type through
    ``round_to``: the weights a deployment one precision lower would hold."""
    import jax
    import jax.numpy as jnp

    class Lowered(cls):
        def build(self, rng, input_shape=None):
            served, lower = jnp.dtype(self.dtype), jnp.dtype(round_to)

            def through(a):
                # the barrier makes the compiler WRITE the lower type: both
                # converts in one fusion, the TPU compiler kept the excess
                # precision and served the weights as built (PERF.md, PR 35)
                return jax.lax.optimization_barrier(a.astype(lower)) \
                    .astype(a.dtype)

            return jax.tree.map(lambda a: through(a) if a.dtype == served else a,
                                super().build(rng, input_shape))

    Lowered.__name__ = cls.__name__
    return Lowered


def main(argv=None, round_to="float8_e4m3fn", **run_main) -> int:
    import run
    from runners import serve_lm
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--round" in argv:
        i = argv.index("--round")
        round_to = argv[i + 1]
        del argv[i:i + 2]
    seen = {}

    class Control(serve_lm.Session):
        def start(self):
            import jax
            config = self.job["config"]
            module, _, name = config["model_class"].partition(":")
            cls = getattr(importlib.import_module(module), name)
            seed = int(self.job["seed"])
            key = jax.random.fold_in(jax.random.PRNGKey(seed >> 16),
                                     seed & 0xFFFF)
            built = jax.jit(cls.from_config(config).build)(key)
            as_built = jax.device_get(built)
            jax.tree.map(lambda a: a.delete(), built)
            setattr(sys.modules[__name__], "Lowered", lowered(cls, round_to))
            self.job = dict(self.job, config=dict(
                config, model_class=f"{__name__}:Lowered"))
            super().start()
            # the system must hold values of the lower type that are not the
            # weights as built, or the run controls nothing
            lower = np.dtype(round_to)
            for got, clean in zip(jax.tree.leaves(self.params),
                                  jax.tree.leaves(as_built)):
                if got.dtype == self.lm.dtype:
                    piece = (slice(0, 64),) * got.ndim
                    held = np.asarray(got[piece])
                    if (held == clean[piece]).all() or not (
                            held.astype(lower).astype(held.dtype) == held).all():
                        raise RuntimeError(
                            f"the served weights are not the weights as built "
                            f"through {round_to}")
            self.params = as_built          # what the reference scores over
            return self

    def run_and_keep(job, run=serve_lm.run):
        seen["facts"] = run(job)
        return seen["facts"]

    serve_lm.Session, serve_lm.run = Control, run_and_keep
    rc = run.main(argv, **run_main)
    facts = seen.get("facts")
    if rc or facts is None:
        return rc or 1
    check = facts["notes"]["check"]
    held = facts["failed"] == 0 and facts["attempted"] > 0 and not check["ok"]
    print(f"control ({round_to}): {'held' if held else 'DID NOT HOLD'}: mean "
          f"{check.get('mean_logit_margin')} of {check.get('mean_tol')}, max "
          f"{check.get('max_logit_margin')} of {check.get('max_tol')}",
          file=sys.stderr)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
