"""CPU rehearsals of the runner ``serve_lm`` over the configuration ``phi-4-mini-flash`` (its
file, its class, its reference, at a toy size), of its two controls, and of the decode
roofline function and its reader against hand counts.

    python -m pytest benchmark/tests/test_serve_lm_ssm_hybrid.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from test_harness import BENCH, RESULT_KEYS, ROOT, _env

sys.path[:0] = [BENCH]
CELL = "phi-4-mini-flash.reason"


def _toy(tmp_path, trace: int, *first, script="toy_ssm_hybrid.py") -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", script),
         str(tmp_path), *first, "--workload", "toy-sh.reason", "--seed",
         "2147483659", "--seconds", "3", "--trace", str(trace)],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _manifest():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _cfg():
    return json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "phi-4-mini-flash.json")))


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
    assert 0 < untraced["window.key_share.reason"] < 100
    # the XLA path (a CPU's) reads every row's whole table: over 100
    assert untraced["shared_kv.read_share.reason"] > 100
    # the cross-decoder runs a row's last position alone: 100 x rows / positions
    assert 0 < untraced["yoco.prefill_cross_share.reason"] < 10
    assert 0 < untraced["ssm.prefill_scan_share.reason"] <= 100


def test_weights_served_through_float8_are_not_correct(tmp_path):
    """The control of ``correct`` (``lower_precision_control.py``; its exit code 0
    = the check came out not ``ok``): the same toy run, served one precision lower."""
    line = _toy(tmp_path, 0, "--control")
    check = line["notes"]["check"]
    assert line["correct"] is False and line["failed"] == 0
    assert check["ok"] is False and check["tokens_checked"] > 10
    assert check["mean_logit_margin"] > check["mean_tol"]


def test_the_state_control_serves_the_mamba_state_in_bfloat16(tmp_path, monkeypatch):
    """``state_precision_control.py`` serves the class with ``state_dtype``
    bfloat16 and runs the cell as ``run.py`` does; the toy run comes back whole."""
    import state_precision_control as spc
    import toy_ssm_hybrid
    from runners import serve_lm
    from toy import cpu_probe

    from analytics_zoo_tpu.models.ssm_hybrid_lm import SSMHybridLM
    toy_ssm_hybrid.build(str(tmp_path))
    # the control swaps the runner's session and run; the test puts them back
    monkeypatch.setattr(serve_lm, "run", serve_lm.run)
    monkeypatch.setattr(serve_lm, "Session", serve_lm.Session)
    rc = spc.main(["--workload", "toy-sh.reason", "--seed", "7", "--seconds", "2",
                   "--trace", "0"], probe=cpu_probe, root=str(tmp_path))
    assert rc == 0 and issubclass(spc.Held, SSMHybridLM)
    assert str(spc.Held.state_dtype) == "bfloat16"


def test_traced_toy_run_reports_every_metric_that_needs_no_chip(tmp_path):
    line = _toy(tmp_path, 1)
    assert line["correct"] is True
    cell = {p["name"]: p for p in _manifest()["per_layer"]
            if CELL in p.get("workloads", ())}
    wanted = {n for n, p in cell.items() if p["source"] != "device_trace"}
    assert len(wanted) >= 9 and wanted <= set(line["metrics"]), \
        wanted - set(line["metrics"])
    assert [n for n in cell if "mfu" in n] == ["step.roofline_mfu.reason"]
    # no chip in the trace: the device readers return nothing and are left out
    assert not [k for k in line["metrics"] if k.startswith("step.")]


def test_the_configuration_file_holds_the_catalog_row_whole():
    cfg, manifest = _cfg(), _manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "phi-4-mini-flash")
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    published = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560, intermediate_size=10240,
        layer_norm_eps=1e-05, max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40, num_hidden_layers=32,
        num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False, vocab_size=200064)
    assert {k: cfg[k] for k in published} == published
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert cfg["deployment"]["slots"] == cfg["generation"]["max_active_slots"] == 32
    assert cfg["generation"]["paged"] and not cfg["generation"]["prefix_cache"]
    cells = [w for w in manifest["workloads"] if w["config"] == "phi-4-mini-flash"]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                          cells[0]["traffic"] + ".json")))
    assert len(traffic["pairs"]) == 64 and traffic["callers"] == 64
    assert all(512 <= p <= 1536 and 1024 <= a <= 2048 and p + a <= 3584
               for p, a in traffic["pairs"])
    assert traffic["generation"]["prefill_buckets"] == [1024, 2048]


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_roofline_counts_the_least_work_by_hand():
    import rooflines_ssm_hybrid as rsh
    cfg = _cfg()
    assert rsh.layer_counts(cfg) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                                     "cross": 7}
    w = rsh.weight_counts(cfg)
    mlp = 3 * 2560 * 10240
    assert w["mamba"] == mlp + 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert w["attention"] == mlp + 2560 * 5120 + 2560 * 2560
    assert w["gmu"] == mlp + 2 * 2560 * 5120 and w["cross"] == mlp + 2 * 2560 * 2560
    assert w["head"] == 200064 * 2560
    matmul = 9 * w["mamba"] + 9 * w["attention"] + 7 * w["gmu"] + 7 * w["cross"] \
        + w["head"]
    assert 3.85e9 < matmul < 3.86e9
    # 100 steps of 32 rows at context 1,800: the shared cache once, 8 windows of 512
    least = rsh.decode_steps_min_seconds(cfg, 100, 3200, [1800] * 32, PEAKS)
    f32 = 9 * w["mamba_f32"] + 9 * w["attention_f32"] + 7 * w["gmu_f32"] \
        + 7 * w["cross_f32"] + w["final_f32"]
    per_row = (1800 + 8 * 512) * 1280 * 2 * 2 + 2 * 9 * 19 * 5120 * 4
    assert least["bytes"] == pytest.approx(100 * (2 * matmul + 4 * f32) + 3200 * per_row)
    assert least["bound"] == "memory"
    assert 10.8 < 1000 * least["seconds"] / 100 < 11.0       # 10.86 ms a step


def _facts(cfg):
    start = {"t": 10.0, "decode_steps": 0, "generated_tokens": 0, "admitted": 0}
    end = {"t": 14.0, "decode_steps": 200, "generated_tokens": 6403, "admitted": 3}
    requests = [{"prompt_len": n, "stamps": [(9.0 + i, 1), (12.0, 251), (15.0, 500)]}
                for i, n in enumerate((600, 1000, 1500))]
    return {"trace": {"program_s": {"jit_pdecode": 2.6, "jit_pprefill": 1.0}},
            "counters": {"trace": [start, end], "window": [start, end]},
            "window": [9.5, 55.0], "requests": requests, "config": cfg,
            "peaks": PEAKS}


def test_the_roofline_reader_reads_the_traced_slice():
    import rooflines_ssm_hybrid as rsh
    from readers import roofline_ssm_hybrid as reader
    from rooflines_window_moe import live_contexts
    cfg = _cfg()
    facts = _facts(cfg)
    got = reader.read(facts, program="jit_pdecode")
    live = live_contexts(facts, 10.0, 14.0)
    assert got == pytest.approx(100 * rsh.decode_steps_min_seconds(
        cfg, 200, 6400, live, PEAKS)["seconds"] / 2.6)
    assert 30 < got < 100
    # no trace, another model: nothing to read
    assert reader.read(dict(facts, trace=None), program="jit_pdecode") is None
    assert reader.read(dict(facts, config={"model": {}}),
                       program="jit_pdecode") is None
