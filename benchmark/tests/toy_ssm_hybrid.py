"""Toy-size rehearsal of the runner ``serve_lm`` over ``phi-4-mini-flash`` on the CPU: a root
of its own with a tiny float32 cut of the real configuration file (same class, same reference,
same keys), a short closed loop, and the real manifest's entries of the cell that
configuration serves, renamed.

    python3 benchmark/tests/toy_ssm_hybrid.py <root> [--control] --workload toy-sh.reason --seed 1 --seconds 2 --trace 0

``--control``: the same run through ``lower_precision_control`` (served weights through
float8): exit code 0 when ``check_served`` came out not ``ok``.  The control's toy serves
float16 (the class's float32 leaves, ``D`` = 1 among them, are then not of the served type,
as at the cell's bfloat16, and the control checks that each served leaf moved).
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

CELL, CONFIG = "toy-sh.reason", "phi-4-mini-flash"


def build(root: str, dtype: str = "float32") -> None:
    import run
    real = run.load_json(run.ROOT, "BENCHMARK.json")
    source = next(c for c in real["configs"] if c["name"] == CONFIG)
    served = next(w for w in real["workloads"] if w["config"] == CONFIG)
    cfg = run.load_json(run.ROOT, source["file"])
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=96, num_hidden_layers=12,
        num_attention_heads=8, num_key_value_heads=4, sliding_window=16, mamba_d_state=4,
        mamba_dt_rank=4, max_position_embeddings=128, dtype=dtype,
        initializer_range=0.3)
    cfg["generation"].update(max_active_slots=4, block_len=4)
    traffic = {"loop": "closed", "callers": 8, "warm_finished": 4,
               "grace_s": 20.0,
               "generation": {"max_prompt_len": 60, "max_tokens": 16,
                              "prefill_buckets": [32, 64]},
               "pairs": [[10, 8], [25, 12], [33, 16], [60, 9], [14, 10], [47, 14]]}
    for rel, doc in (("configs/toy-sh.json", cfg), ("traffic/reason.json", traffic)):
        path = os.path.join(root, "benchmark", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
    manifest = dict(real)
    manifest["workloads"] = [{"name": CELL, "config": "toy-sh",
                              "traffic": "reason", "chips": 1}]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for m in manifest[kind]:
            if "workloads" in m and served["name"] not in m["workloads"]:
                continue
            kept.append(dict(m, workloads=[CELL]) if "workloads" in m else m)
        manifest[kind] = kept
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


if __name__ == "__main__":
    import run
    from toy import cpu_probe
    build(sys.argv[1], "float16" if sys.argv[2] == "--control" else "float32")
    if sys.argv[2] == "--control":
        import lower_precision_control
        sys.exit(lower_precision_control.main(sys.argv[3:], probe=cpu_probe,
                                              root=sys.argv[1]))
    sys.exit(run.main(sys.argv[2:], probe=cpu_probe, root=sys.argv[1]))
