"""Toy-size rehearsal of ``benchmark/run.py`` on the CPU: builds a root of its own
(a BENCHMARK.json with the real metric entries, toy configs and traffic) and runs
``run.main`` with the device refusal lifted HERE ONLY (a probe that accepts the CPU).

    python3 benchmark/tests/toy.py <root> --workload toy-lm.chatty --seed 1 --seconds 2 --trace 0
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

RENAME = {"gpt2-large.chat": "toy-lm.chatty", "gpt2-large.batch": "toy-lm.batchy"}
PAIRS = [[5, 8], [9, 12], [17, 16], [30, 9], [12, 10], [20, 14]]


def build(root: str) -> None:
    import run
    real = run.load_json(run.ROOT, "BENCHMARK.json")
    lm = run.load_json(BENCH, "configs", "gpt2-large.json")
    lm["model"].update(n_layer=2, n_embd=64, n_head=4, n_positions=128,
                       vocab_size=503)
    lm["generation"]["max_active_slots"] = 4
    resnet = {"runner": "train_fit", "batch_per_chip": 4,
              "dtype_policy": "mixed_bf16",
              "loss": "sparse_categorical_crossentropy",
              "optimizer": {"lr": 0.01, "momentum": 0.9},
              "model": {"depth": 18, "image_size": 32, "num_classes": 10,
                        "stem": "s2d"}}
    gen = {"max_prompt_len": 32, "max_tokens": 16, "prefill_buckets": [16, 32]}
    chatty = {"loop": "open", "rate_rps": 20.0, "warm_finished": 5,
              "grace_s": 10.0, "generation": gen, "pairs": PAIRS,
              "gaps_unit": [0.5, 1.5, 1.0, 0.2, 1.8, 1.0]}
    batchy = {"loop": "closed", "callers": 8, "warm_finished": 4,
              "grace_s": 10.0, "generation": gen, "pairs": PAIRS}
    fit = {"steps_per_call": 1, "lag": 2, "warm_calls": 7, "dataset_batches": 4}
    files = {"configs/toy-lm.json": lm, "configs/toy-resnet.json": resnet,
             "traffic/chatty.json": chatty, "traffic/batchy.json": batchy,
             "traffic/fit.json": fit}
    for rel, doc in files.items():
        path = os.path.join(root, "benchmark", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
    manifest = dict(real)
    manifest["workloads"] = [
        {"name": "toy-lm.chatty", "config": "toy-lm", "traffic": "chatty",
         "chips": 1},
        {"name": "toy-lm.batchy", "config": "toy-lm", "traffic": "batchy",
         "chips": 1},
        {"name": "toy-resnet.fit", "config": "toy-resnet", "traffic": "fit",
         "chips": 4}]
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            if "workloads" in m:
                m["workloads"] = [RENAME.get(w, w) for w in m["workloads"]]
    for m in manifest["end_to_end"]:          # the toy fit cell reports the rate too
        if "workloads" in m and "toy-lm.batchy" in m["workloads"]:
            m["workloads"].append("toy-resnet.fit")
    # the training runner's per-layer metrics, until a cell of the real manifest
    # reports them
    listed = {m["name"] for m in manifest["per_layer"]}
    manifest["per_layer"] += [
        {"name": name, "unit": unit, "source": source,
         "workloads": ["toy-resnet.fit"]}
        for name, unit, source in (
            ("fit.step_ms", "ms", "host_clock"), ("train.mfu", "%", "host_clock"),
            ("collective.time_share", "%", "device_trace"))
        if name not in listed]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


def cpu_probe(chips: int) -> dict:
    import jax
    import run
    d = jax.devices()[0]
    peaks = run.load_json(BENCH, "peaks.json")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "peaks": next(iter(peaks.values()))}


if __name__ == "__main__":
    import run
    build(sys.argv[1])
    sys.exit(run.main(sys.argv[2:], probe=cpu_probe, root=sys.argv[1]))
