"""Toy-size rehearsal of the runner ``serve_lm`` on the CPU: a root of its own with a
tiny float32 configuration of the class the real configuration names, a short closed
loop, and the real manifest's entries of the cell that configuration serves, renamed.

    python3 benchmark/tests/toy_lm.py <root> --workload toy-moe.docs --seed 1 --seconds 2 --trace 0
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

CELL = "toy-moe.docs"


def build(root: str) -> None:
    import run
    real = run.load_json(run.ROOT, "BENCHMARK.json")
    source = next(c for c in real["configs"] if c["reduced"])
    served = next(w for w in real["workloads"] if w["config"] == source["name"])
    cfg = run.load_json(run.ROOT, source["file"])
    cfg.update(
        vocab_size=97, hidden_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=4, num_experts_per_tok=3, num_attention_heads=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, index_n_heads=4, index_head_dim=8, index_topk=6,
        max_position_embeddings=64, published={"n_routed_experts": 8},
        deployment={"chip": 1}, dtype="float32", initializer_range=0.3)
    cfg["generation"].update(max_active_slots=4, block_len=4)
    traffic = {"loop": "closed", "callers": 8, "warm_finished": 4,
               "grace_s": 20.0,
               "generation": {"max_prompt_len": 30, "max_tokens": 16,
                              "prefill_buckets": [16, 32]},
               "pairs": [[5, 8], [9, 12], [17, 16], [30, 9], [12, 10], [20, 14]]}
    for rel, doc in (("configs/toy-moe.json", cfg), ("traffic/docs.json", traffic)):
        path = os.path.join(root, "benchmark", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
    manifest = dict(real)
    manifest["workloads"] = [{"name": CELL, "config": "toy-moe",
                              "traffic": "docs", "chips": 1}]
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
    build(sys.argv[1])
    sys.exit(run.main(sys.argv[2:], probe=cpu_probe, root=sys.argv[1]))
