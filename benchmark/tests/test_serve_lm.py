"""CPU rehearsals of the runner ``serve_lm``, its reference's verdict and the decode
step's roofline arithmetic.

    python -m pytest benchmark/tests/test_serve_lm.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from test_harness import BENCH, RESULT_KEYS, ROOT, _env

sys.path[:0] = [BENCH]


def _toy(tmp_path, trace: int) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "toy_lm.py"), str(tmp_path),
         "--workload", "toy-moe.docs", "--seed", "2147483659", "--seconds", "3",
         "--trace", str(trace)],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _cell_metrics():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    source = next(c for c in manifest["configs"] if c["reduced"])
    cell = next(w["name"] for w in manifest["workloads"]
                if w["config"] == source["name"])
    return {p["name"]: p for p in manifest["per_layer"]
            if cell in p.get("workloads", ())}


def test_last_line_of_a_toy_run_of_the_new_class(tmp_path):
    line = _toy(tmp_path, 0)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    check = line["notes"]["check"]
    assert check["checked"] == 2 and check["tokens_checked"] > 10
    assert check["mean_logit_margin"] <= 1e-3      # float32 against float32
    untraced = line["notes"]["per_layer_untraced"]
    assert untraced["moe.held_pair_share.longdoc"] > 0


def test_traced_toy_run_reports_every_metric_that_needs_no_chip(tmp_path):
    line = _toy(tmp_path, 1)
    assert line["correct"] is True
    wanted = {n for n, p in _cell_metrics().items()
              if p["source"] != "device_trace"}
    assert len(wanted) >= 8 and wanted <= set(line["metrics"]), \
        wanted - set(line["metrics"])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # 4 of 8 experts held, every token picks 3 of 8; top-6 of contexts 5-46
    assert 20 < got["moe.held_pair_share.longdoc"] < 80
    assert 0 < got["dsa.selected_share.longdoc"] < 100
    assert got["moe.busiest_expert_load.longdoc"] >= 1.0
    assert 0 < got["sched.slot_occupancy.longdoc"] <= 100
    # no chip in the trace: the device readers return nothing and are left out
    assert not [k for k in line["metrics"] if k.startswith("step.")]


def _facts(cfg, touched=True):
    end = {"t": 14.0, "decode_steps": 400, "generated_tokens": 6500,
           "admitted": 100, "model.moe_experts_touched": 12800}
    start = {"t": 10.0, "decode_steps": 0, "generated_tokens": 0, "admitted": 0,
             "model.moe_experts_touched": 0}
    if not touched:
        del end["model.moe_experts_touched"], start["model.moe_experts_touched"]
    requests = [{"prompt_len": n,
                 "stamps": [(9.0, 1), (12.0, 251), (15.0, 500)]}
                for n in (1000, 5000)]
    return {"trace": {"program_s": {"jit_pdecode": 3.6, "jit_pprefill": 0.3}},
            "counters": {"trace": [start, end]}, "requests": requests,
            "config": cfg,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_decode_roofline_counts_the_bytes_a_step_needs():
    import rooflines_lm
    from readers import roofline_lm_decode
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(ROOT, next(
        c["file"] for c in manifest["configs"] if c["reduced"]))))
    w = rooflines_lm.weight_counts(cfg)
    # the issue's table: MLA 165.0 M + indexer 9.4 M, one expert 37.75 M
    assert w["layer_attention"] == 164_954_112 + 9_437_184
    assert w["expert"] == 3 * 6144 * 2048 and w["shared"] == w["expert"]
    assert w["router"] == 6144 * 256 and w["head"] == 6144 * 19360
    least = rooflines_lm.decode_steps_min_seconds(
        cfg, 400, 6400, 12800, 7501.0, 3001.0 + 1048.0, _facts(cfg)["peaks"])
    assert least["bound"] == "memory"
    step = (6 * w["layer_attention"] + w["dense_ffn"] + 5 * w["shared"]
            + w["head"]) * 2 + 5 * w["router"] * 4 \
        + 6 * (7501 * 128 + 4049 * 576) * 2
    assert least["bytes"] == pytest.approx(400 * step + 12800 * w["expert"] * 2)
    facts = _facts(cfg)
    # contexts 1,001 then 1,251 and 5,001 then 5,251, half the slice each: the
    # first under the selection's 2,048, the second capped at it
    value = roofline_lm_decode.read(facts)
    live = rooflines_lm.live_context(facts, 10.0, 14.0)
    assert live == pytest.approx(6002 + 250, abs=3)
    assert rooflines_lm.live_context(facts, 10.0, 14.0, cap=2048) \
        == pytest.approx(1001 + 125 + 2048, abs=2)
    assert 50 < value < 100
    assert value == pytest.approx(100 * rooflines_lm.decode_steps_min_seconds(
        cfg, 400, 6400, 12800, live,
        rooflines_lm.live_context(facts, 10.0, 14.0, cap=2048),
        facts["peaks"])["seconds"] / 3.6)
    # a program without the counters (the parent), no trace, another model: nothing
    assert roofline_lm_decode.read(_facts(cfg, touched=False)) is None
    assert roofline_lm_decode.read(dict(facts, trace=None)) is None
    assert roofline_lm_decode.read(dict(facts, config={"model": {}})) is None
