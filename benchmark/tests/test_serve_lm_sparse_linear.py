"""CPU rehearsals of the runner ``serve_lm`` over the configuration ``minicpm-sala``
(its file, its class, its reference, at a toy size) and of the two roofline functions
against hand counts.

    python -m pytest benchmark/tests/test_serve_lm_sparse_linear.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from test_harness import BENCH, RESULT_KEYS, ROOT, _env

sys.path[:0] = [BENCH]
CELL = "minicpm-sala.longctx"


def _toy(tmp_path, trace: int, *first) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "toy_sparse_linear.py"),
         str(tmp_path), *first, "--workload", "toy-sala.docs", "--seed",
         "2147483659", "--seconds", "3", "--trace", str(trace)],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _manifest():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _cfg():
    return json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "minicpm-sala.json")))


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
    assert 0 < untraced["sparse.kept_block_share.longctx"] < 100
    assert untraced["sparse.dense_row_share.longctx"] == 0   # every context > 16


def test_weights_served_through_float8_are_not_correct(tmp_path):
    """The control of ``correct`` (``lower_precision_control.py``; its exit code 0
    = the check came out not ``ok``): the same toy run, served one precision lower."""
    line = _toy(tmp_path, 0, "--control")
    check = line["notes"]["check"]
    assert line["correct"] is False and line["failed"] == 0
    assert check["ok"] is False and check["tokens_checked"] > 10
    assert check["mean_logit_margin"] > check["mean_tol"]
    assert check["max_logit_margin"] > check["max_tol"]


def test_traced_toy_run_reports_every_metric_that_needs_no_chip(tmp_path):
    line = _toy(tmp_path, 1)
    assert line["correct"] is True
    cell = {p["name"]: p for p in _manifest()["per_layer"]
            if CELL in p.get("workloads", ())}
    wanted = {n for n, p in cell.items() if p["source"] != "device_trace"}
    assert len(wanted) >= 7 and wanted <= set(line["metrics"]), \
        wanted - set(line["metrics"])
    # the prefill program's share of the peak is held back (PERF.md section 7)
    assert [n for n in cell if "mfu" in n] == ["step.roofline_mfu.longctx"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 25 < got["sparse.kept_block_share.longctx"] < 80   # 4 of 6-19 blocks
    assert 0 < got["sched.slot_occupancy.longctx"] <= 100
    # no chip in the trace: the device readers return nothing and are left out
    assert not [k for k in line["metrics"] if k.startswith("step.")]


def test_the_configuration_file_states_the_cut():
    cfg, manifest = _cfg(), _manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "minicpm-sala")
    row = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"MiniCPM-SALA"' in l] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "mixer_types"]
    for published in (r["config"] for r in row):
        assert entry["source"] == cfg["source"] == row[0]["source_url"]
        for key, value in published.items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
        assert cfg["mixer_types"] == published["mixer_types"][9:21]
    assert cfg["num_hidden_layers"] == len(cfg["mixer_types"]) == 12
    assert cfg["mixer_types"].count("minicpm4") * 3 \
        == cfg["mixer_types"].count("lightning-attn")
    assert cfg["deployment"]["layers"] == [9, 20] and cfg["assumed"]
    cells = [w for w in manifest["workloads"] if w["config"] == "minicpm-sala"]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                          cells[0]["traffic"] + ".json")))
    assert len(traffic["pairs"]) == 32 and traffic["callers"] == 32
    assert all(p + a <= 30720 and p > cfg["sparse_config"]["dense_len"]
               for p, a in traffic["pairs"])


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_rooflines_count_the_least_work_by_hand():
    import rooflines_sparse_linear as rsl
    cfg = _cfg()
    w = rsl.weight_counts(cfg)
    # the issue's table: an attention layer 253.8 M, a linear layer 285.2 M
    assert w["sparse"] == 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384 \
        == 253_755_392
    assert w["linear"] == 5 * 4096 * 4096 + 3 * 4096 * 16384 == 285_212_672
    assert w["head"] == 4096 * 73448
    assert rsl.layer_counts(cfg) == (3, 9)
    # a query at context 20,000: 64 blocks kept of 313, its own holds 32 tokens
    assert rsl.kept_tokens(cfg, 20000) == 63 * 64 + 32
    assert rsl.kept_tokens(cfg, 8192) == 8192 and rsl.kept_tokens(cfg, 1) == 1
    assert rsl.windows_seen(cfg, 20000) == (20000 - 32) // 16 + 1
    assert rsl.windows_seen(cfg, 31) == 0 and rsl.windows_seen(cfg, 32) == 1
    # a decode step of 16 rows at context 20,000
    least = rsl.decode_steps_min_seconds(cfg, 100, 1600, [20000] * 16, PEAKS)
    weights = (3 * w["sparse"] + 9 * w["linear"] + w["head"]) * 2
    row = 9 * 2 * 32 * 128 * 128 * 4 \
        + 3 * 2 * 128 * 2 * (1249 + 2 * 64 * 64)
    assert least["bound"] == "memory"
    assert least["bytes"] == pytest.approx(100 * weights + 1600 * row)
    assert weights == pytest.approx(7.258e9, rel=1e-3)    # the embedding is not read
    assert 0.0097 < least["seconds"] / 100 < 0.0100       # 9.9 ms a step
    # a prompt of 20,000 positions: the matmuls, the recurrence, 3 attention layers
    pre = rsl.prefill_min_seconds(cfg, [20000], PEAKS)
    per_position = 3 * w["sparse"] + 9 * w["linear"] + 9 * 2 * 32 * 128 * 128
    attention = 0
    for c in range(1, 20001):
        own = (c - 1) % 64 + 1
        attention += 2 * c if c <= 8192 else \
            ((c - 32) // 16 + 1) + 2 * (63 * 64 + own)
    assert pre["flops"] == pytest.approx(
        2.0 * (20000 * per_position + w["head"] + 3 * 32 * 128 * attention))
    assert pre["bound"] == "compute" and 0.65 < pre["seconds"] < 0.75


def _facts(cfg, counters=True):
    start = {"t": 10.0, "decode_steps": 0, "generated_tokens": 0, "admitted": 0}
    end = {"t": 14.0, "decode_steps": 200, "generated_tokens": 3203,
           "admitted": 3}
    if not counters:
        del end["decode_steps"]
    requests = [{"prompt_len": n, "stamps": [(9.0 + i, 1), (12.0, 251), (15.0, 500)]}
                for i, n in enumerate((12000, 20000, 28000))]
    return {"trace": {"program_s": {"jit_pdecode": 2.6, "jit_pprefill": 5.0}},
            "counters": {"trace": [start, end], "window": [start, end]},
            "window": [9.5, 55.0], "requests": requests, "config": cfg,
            "peaks": PEAKS}


def test_the_roofline_reader_reads_the_traced_slice():
    import rooflines_sparse_linear as rsl
    from readers import roofline_sparse_linear as reader
    cfg = _cfg()
    facts = _facts(cfg)
    decode = reader.read(facts, program="jit_pdecode")
    live = rsl.live_contexts(facts, 10.0, 14.0)
    assert len(live) == 137 and 12000 < min(live) < 12300 < 28000 < max(live)
    assert decode == pytest.approx(100 * rsl.decode_steps_min_seconds(
        cfg, 200, 3200, live, PEAKS)["seconds"] / 2.6)
    assert 50 < decode < 100
    # a program without the counters, no trace, another model: nothing to read
    assert reader.read(_facts(cfg, counters=False), program="jit_pdecode") is None
    assert reader.read(dict(facts, trace=None), program="jit_pdecode") is None
    assert reader.read(dict(facts, config={"model": {}}),
                       program="jit_pdecode") is None
