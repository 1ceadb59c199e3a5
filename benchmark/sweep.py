#!/usr/bin/env python3
"""The sweep that fixes an open-loop cell's rate, run once when the cell is defined.

    python3 benchmark/sweep.py --workload <cell> --rates 3,4,5,6 --seconds 20 [--out f]

One process, one set-up; each rate gets warm traffic and then a window of
``--seconds``.  Per rate it prints the tokens/s offered (the answer lengths of the
window's requests) and completed (stamped inside the window), the client's TTFT and
TPOT, and the scheduler's waiting queue read when the window is over.  The knee is
the highest rate at which completed stays within 2 % of offered and the waiting
queue is no longer than the slots; the cell's ``rate_rps`` is 0.8 x knee, written
into the traffic file by hand together with the table (PERF.md section 4).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run as bench                                    # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    _, cell, config, traffic = bench.load_cell(bench.ROOT, args.workload)
    device = bench.device_or_refuse(cell["chips"])
    from analytics_zoo_tpu.inference import aot
    aot.enable_persistent_cache()
    from readers import client_latency, rate
    from runners import serve_generate
    job = {"config": config, "traffic": traffic, "chips": cell["chips"],
           "seed": args.seed, "trace": False, "peaks": device["peaks"],
           "t_process": T_PROCESS}
    session = serve_generate.Session(job).start()
    rows = []
    try:
        for i, r in enumerate(float(x) for x in args.rates.split(",")):
            m = session.measure(args.seconds, rate_rps=r, run_id=f"s{i}")
            facts = {**m, "chips": cell["chips"]}
            mine = [q for q in m["requests"] if q["in_window"]]
            row = {"rate_rps": r, "requests": m["attempted"],
                   "failed": m["failed"],
                   "offered_tokens_per_s": sum(q["answer_len"] for q in mine)
                   / args.seconds,
                   "completed_tokens_per_s": rate.read(facts),
                   "client_ttft_p50": client_latency.read(facts, "ttft", "p50"),
                   "client_ttft_p95": client_latency.read(facts, "ttft", "p95"),
                   "client_tpot_p50": client_latency.read(facts, "tpot", "p50"),
                   "generator_late_p99": client_latency.read(facts, "late", "p99"),
                   "waiting_at_end": m["waiting_at_end"],
                   "slots": config["generation"]["max_active_slots"],
                   "compiles": m["compiles_in_window"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
            # let the queue empty before the next rate
            deadline = time.monotonic() + 60.0
            while not session.serving._batcher.idle \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
    finally:
        session.close()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": {k: device[k] for k in
                                  ("platform", "kind", "count")},
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
