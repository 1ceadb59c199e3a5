"""A second control of the ``phi-4-mini-flash`` cell's ``correct``: the cell run as ``run.py``
runs it, except that the served class holds the Mamba layers' ``conv`` and ``ssm`` state in
``--state`` (bfloat16) in place of float32, rounded at every token, while the reference
keeps its state in float32.  It prints ``run.py``'s line and says whether ``check_served``
saw the difference; either way is a finding (``PERF.md``): where the control passes, the
CPU test that pins the state's type is what guards it.

    python3 benchmark/tests/state_precision_control.py --workload phi-4-mini-flash.reason \
        --seed 3500000301 --seconds 45 [--state bfloat16]

Exit code 0 when the run came back whole (every request finished), whatever the check said.
"""

from __future__ import annotations

import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main(argv=None, state="bfloat16", **run_main) -> int:
    import jax.numpy as jnp

    import run
    from runners import serve_lm
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--state" in argv:
        i = argv.index("--state")
        state = argv[i + 1]
        del argv[i:i + 2]
    seen = {}
    Session = serve_lm.Session

    class Control(Session):
        def start(self):
            config = self.job["config"]
            module, _, name = config["model_class"].partition(":")
            cls = getattr(importlib.import_module(module), name)
            held = type(cls.__name__, (cls,), {"state_dtype": jnp.dtype(state)})
            setattr(sys.modules[__name__], "Held", held)
            self.job = dict(self.job, config=dict(
                config, model_class=f"{__name__}:Held"))
            return super().start()

    def run_and_keep(job, run=serve_lm.run):
        seen["facts"] = run(job)
        return seen["facts"]

    serve_lm.Session, serve_lm.run = Control, run_and_keep
    rc = run.main(argv, **run_main)
    facts = seen.get("facts")
    if rc or facts is None:
        return rc or 1
    check = facts["notes"]["check"]
    print(f"control (state in {state}): check {'passed' if check['ok'] else 'failed'}: "
          f"mean {check.get('mean_logit_margin')} of {check.get('mean_tol')}, max "
          f"{check.get('max_logit_margin')} of {check.get('max_tol')}", file=sys.stderr)
    return 0 if facts["failed"] == 0 and facts["attempted"] > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
