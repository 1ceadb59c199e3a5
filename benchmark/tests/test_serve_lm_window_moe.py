"""CPU rehearsals of the runner ``serve_lm`` over the configuration ``smallthinker-21b-a3b``
(its file, its class, its reference, at a toy size) and of the decode roofline function
and its reader against hand counts.

    python -m pytest benchmark/tests/test_serve_lm_window_moe.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from test_harness import BENCH, RESULT_KEYS, ROOT, _env

sys.path[:0] = [BENCH]
CELL = "smallthinker-21b-a3b.docmix"


def _toy(tmp_path, trace: int, *first) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "toy_window_moe.py"),
         str(tmp_path), *first, "--workload", "toy-st.docs", "--seed",
         "2147483659", "--seconds", "3", "--trace", str(trace)],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _manifest():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _cfg():
    return json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "smallthinker-21b-a3b.json")))


def test_last_line_of_a_toy_run_of_the_new_class(tmp_path):
    line = _toy(tmp_path, 0)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    check = line["notes"]["check"]
    assert check["checked"] == 2 and check["tokens_checked"] > 10
    assert check["mean_logit_margin"] <= 1e-4      # float32 against float32
    untraced = line["notes"]["per_layer_untraced"]
    # contexts of 10-76 against a window of 16: most decode rows past it
    assert 0 < untraced["window.key_share.docmix"] < 100
    # a toy bucket (32, 64) is one query block of the served sizes, whose span
    # (window + block) covers it whole: every chunk up to the diagonal runs
    assert untraced["window.prefill_chunk_share.docmix"] == 100
    assert 0 < untraced["moe.experts_touched_share.docmix"] <= 100
    assert untraced["moe.busiest_expert_load.docmix"] >= 1


def test_weights_served_through_float8_are_not_correct(tmp_path):
    """The control of ``correct`` (``lower_precision_control.py``; its exit code 0
    = the check came out not ``ok``): the same toy run, served one precision lower."""
    line = _toy(tmp_path, 0, "--control")
    check = line["notes"]["check"]
    assert line["correct"] is False and line["failed"] == 0
    assert check["ok"] is False and check["tokens_checked"] > 10
    assert check["mean_logit_margin"] > check["mean_tol"]


def test_traced_toy_run_reports_every_metric_that_needs_no_chip(tmp_path):
    line = _toy(tmp_path, 1)
    assert line["correct"] is True
    cell = {p["name"]: p for p in _manifest()["per_layer"]
            if CELL in p.get("workloads", ())}
    wanted = {n for n, p in cell.items() if p["source"] != "device_trace"}
    assert len(wanted) >= 9 and wanted <= set(line["metrics"]), \
        wanted - set(line["metrics"])
    assert [n for n in cell if "mfu" in n] == ["step.roofline_mfu.docmix"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < got["sched.slot_occupancy.docmix"] <= 100
    # no chip in the trace: the device readers return nothing and are left out
    assert not [k for k in line["metrics"] if k.startswith("step.")]


def test_the_configuration_file_states_the_cut():
    cfg, manifest = _cfg(), _manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    cut = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert entry["reduced"] == cfg["reduced"] == cut
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
    # the published widths stand, the published layouts are 13 periods of 4
    published = dict(hidden_size=2560, num_attention_heads=28, num_key_value_heads=4,
                     head_dim=128, moe_ffn_hidden_size=768, moe_num_primary_experts=64,
                     moe_num_active_primary_experts=6, sliding_window_size=4096,
                     vocab_size=151936, max_position_embeddings=16384)
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "rope_layout": [0, 1, 1, 1] * 13,
                                "sliding_window_layout": [0, 1, 1, 1] * 13}
    for key in cut[1:]:
        assert cfg[key] == cfg["published"][key][:8] == [0, 1, 1, 1] * 2
    assert cfg["num_hidden_layers"] == 8
    assert cfg["deployment"]["layers"] == [0, 7] and cfg["assumed"]
    assert cfg["generation"]["paged"] and not cfg["generation"]["prefix_cache"]
    cells = [w for w in manifest["workloads"] if w["config"] == "smallthinker-21b-a3b"]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                          cells[0]["traffic"] + ".json")))
    assert len(traffic["pairs"]) == 32 and traffic["callers"] == 32
    assert all(p + a <= 15360 for p, a in traffic["pairs"])
    assert sum(p <= cfg["sliding_window_size"] for p, _ in traffic["pairs"]) == 8


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_roofline_counts_the_least_work_by_hand():
    import rooflines_window_moe as rwm
    cfg = _cfg()
    w = rwm.weight_counts(cfg)
    # attention 20,971,520 a layer (q, o 2560 x 3584; k, v 2560 x 512), an expert 5,898,240
    assert w["attention"] == 20_971_520 and w["expert"] == 5_898_240
    assert w["router"] == 2560 * 64 and w["head"] == 2560 * 151936
    assert rwm.layer_counts(cfg) == (2, 6)
    # 100 steps of 16 rows at context 8,000, 51 experts touched a layer
    least = rwm.decode_steps_min_seconds(cfg, 100, 1600, 100 * 51 * 8,
                                         [8000] * 16, PEAKS)
    weights = (8 * 20_971_520 + 2560 * 151936) * 2 + 8 * 2560 * 64 * 4
    keys = 2 * 8000 + 6 * 4096
    assert least["bytes"] == pytest.approx(
        100 * weights + 100 * 51 * 8 * 5_898_240 * 2 + 1600 * keys * 4 * 128 * 4)
    assert least["bound"] == "memory"
    assert 8.7 < 1000 * least["seconds"] / 100 < 9.0       # 8.86 ms a step
    per_token = 8 * (20_971_520 + 2560 * 64 + 6 * 5_898_240) + 2560 * 151936 \
        + keys * 28 * 128 * 2
    assert least["flops"] == pytest.approx(2.0 * 1600 * per_token)


def _facts(cfg, counters=True):
    start = {"t": 10.0, "decode_steps": 0, "generated_tokens": 0, "admitted": 0,
             "model.moe_experts_touched": 0}
    end = {"t": 14.0, "decode_steps": 200, "generated_tokens": 3203,
           "admitted": 3, "model.moe_experts_touched": 200 * 8 * 50}
    if not counters:
        del end["model.moe_experts_touched"]
    requests = [{"prompt_len": n, "stamps": [(9.0 + i, 1), (12.0, 251), (15.0, 500)]}
                for i, n in enumerate((2000, 8000, 14000))]
    return {"trace": {"program_s": {"jit_pdecode": 2.6, "jit_pprefill": 5.0}},
            "counters": {"trace": [start, end], "window": [start, end]},
            "window": [9.5, 55.0], "requests": requests, "config": cfg,
            "peaks": PEAKS}


def test_the_roofline_reader_reads_the_traced_slice():
    import rooflines_window_moe as rwm
    from readers import roofline_window_moe as reader
    cfg = _cfg()
    facts = _facts(cfg)
    got = reader.read(facts, program="jit_pdecode")
    live = rwm.live_contexts(facts, 10.0, 14.0)
    assert len(live) == 137 and 2000 < min(live) < 2300 < 14000 < max(live)
    assert got == pytest.approx(100 * rwm.decode_steps_min_seconds(
        cfg, 200, 3200, 200 * 8 * 50, live, PEAKS)["seconds"] / 2.6)
    assert 30 < got < 100
    # a program without the counters, no trace, another model: nothing to read
    assert reader.read(_facts(cfg, counters=False), program="jit_pdecode") is None
    assert reader.read(dict(facts, trace=None), program="jit_pdecode") is None
    assert reader.read(dict(facts, config={"model": {}}),
                       program="jit_pdecode") is None
