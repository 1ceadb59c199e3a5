"""Fast CPU rehearsals of the harness (seconds each; none of this is under tests/).

    python -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _env(devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _toy(tmp_path, cell: str, trace: int, devices: int = 1) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "toy.py"), str(tmp_path),
         "--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--trace", str(trace)],
        env=_env(devices), capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices,reports", [
    ("toy-lm.chatty", 1, {"ttft_ms", "tpot_ms", "setup_s"}),
    ("toy-lm.batchy", 1, {"throughput", "setup_s"}),
    ("toy-resnet.fit", 4, {"throughput", "setup_s"}),
])
def test_last_line_of_a_toy_run(tmp_path, cell, devices, reports):
    line = _toy(tmp_path, cell, 0, devices)
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == reports
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    assert line["device"]["count"] == devices


def test_traced_toy_fit_reports_the_training_metrics(tmp_path):
    line = _toy(tmp_path, "toy-resnet.fit", 1, devices=4)
    assert {"fit.step_ms", "train.mfu"} <= set(line["metrics"])
    assert "collective.time_share" not in line["metrics"]   # no chip traced
    assert line["correct"] is True


def test_traced_toy_run_reports_per_layer_metrics(tmp_path):
    line = _toy(tmp_path, "toy-lm.chatty", 1)
    # no chip in the trace: the device readers return nothing and are left out
    assert {"client.late_p99_ms", "client.ttft_p95_ms", "sched.ttft_ms",
            "sched.slot_occupancy.chat", "engine.ttft_outside_sched_ms"} \
        <= set(line["metrics"])
    assert not any("roofline" in k or k.startswith("step.")
                   for k in line["metrics"])
    assert 0 < line["metrics"]["sched.slot_occupancy.chat"]["value"] <= 100


def test_refuses_without_a_tpu():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
         ["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        env=_env(), capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "refused" in done.stderr


@pytest.mark.parametrize("traffic_file", sorted(
    f for f in os.listdir(os.path.join(BENCH, "traffic"))))
def test_every_pass_sends_the_tables_once_in_a_fixed_order(traffic_file):
    import loadgen
    traffic = json.load(open(os.path.join(BENCH, "traffic", traffic_file)))
    n = len(traffic["pairs"])
    plans = [loadgen.plan(traffic, loadgen.MAIN) for _ in range(2)]
    first = [next(plans[0]) for _ in range(2 * n)]
    assert first == [next(plans[1]) for _ in range(2 * n)]    # no seed in it
    for rows in (first[:n], first[n:]):                         # each pass
        assert Counter((p, a) for _, p, a in rows) \
            == Counter(map(tuple, traffic["pairs"]))
    assert first[:n] != first[n:]            # a pass has an order of its own
    warm = loadgen.plan(traffic, loadgen.WARM)
    assert [next(warm) for _ in range(n)] != first[:n]
    if traffic["loop"] == "open":
        unit = Counter(round(g * traffic["rate_rps"], 5) for g, _, _ in first[:n])
        scale = n / sum(traffic["gaps_unit"])
        assert unit == Counter(round(g * scale, 5) for g in traffic["gaps_unit"])
        # one pass of the tables at the cell's rate is one full-length window
        manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
        total = sum(g for g, _, _ in first[:n])
        assert total == pytest.approx(manifest["run_seconds"], rel=1e-9)
        assert total <= manifest["run_seconds"] + 1e-6


def test_token_ids_are_a_function_of_seed_and_index():
    import loadgen
    a = loadgen.token_ids(2 ** 31 + 5, 7, 40, 50257)
    assert (a == loadgen.token_ids(2 ** 31 + 5, 7, 40, 50257)).all()
    assert (a != loadgen.token_ids(2 ** 31 + 5, 8, 40, 50257)).any()
    assert a.min() >= 1 and a.max() < 50257


def _request(due, stamps, in_window=True, ok=True, prompt_len=10):
    return {"due": due, "sent": due + 0.001, "in_window": in_window, "ok": ok,
            "stamps": stamps, "prompt_len": prompt_len,
            "answer_len": stamps[-1][1]}


def test_a_request_due_inside_and_finished_after_counts_once():
    from readers import client_latency, rate
    # window [10, 20): request A lies inside; B is due at 19 and finishes at 21;
    # C was due before the window (warm traffic) and finishes inside it
    a = _request(11.0, [(11.2, 4), (11.6, 8)])
    b = _request(19.0, [(19.5, 4), (21.0, 12)])
    c = _request(9.0, [(9.5, 4), (10.5, 8)], in_window=False)
    work = [(11.2, 4), (11.6, 4), (19.5, 4), (21.0, 8), (9.5, 4), (10.5, 4)]
    facts = {"window": [10.0, 20.0], "requests": [a, b, c], "work": work,
             "chips": 1}
    assert len(client_latency.sample(facts, "ttft")) == 2      # A and B, once
    assert client_latency.read(facts, "ttft", "p50") == pytest.approx(350.0)
    # tokens count by when they were emitted: B's last 8 fall outside, C's 4 inside
    assert rate.read(facts) == pytest.approx((4 + 4 + 4 + 4) / 10.0)
    assert client_latency.read(facts, "tpot", "mean") == pytest.approx(
        (400.0 / 4 + 1500.0 / 8) / 2)


def test_trace_reduction_gives_the_known_idle_share():
    import xplane
    from readers import trace_time
    # window [1, 11): a while of 4 s holding two body ops, then a 2 s op: busy
    # is the UNION (6 s), not the sum (9 s); the longest gap is 3 s
    ops = [("while", 2.0, 6.0), ("fusion", 2.0, 3.5), ("paged_attention", 3.5, 5.0),
           ("all-reduce", 9.0, 11.0), ("fusion", 0.0, 0.5)]
    loaded = {"devices": {0: {"ops": ops, "programs": [("jit_step", 2.0, 6.0)]}},
              "spans": [(xplane.WINDOW_SPAN, 1.0, 11.0),
                        ("bench.send", 6.5, 8.9)]}
    doc = xplane.reduce(loaded, 1)
    assert doc["window_s"] == pytest.approx(10.0)
    assert doc["busy_s"] == pytest.approx(6.0)
    facts = {"trace": doc}
    assert trace_time.read(facts, per="window", scale=100.0,
                           complement=True) == pytest.approx(40.0)
    assert trace_time.read(facts, ops=["all-reduce"], per="window",
                           scale=100.0) == pytest.approx(20.0)
    assert trace_time.read(facts, programs=["jit_step"], per="busy",
                           scale=100.0) == pytest.approx(100 * 4 / 6)
    assert doc["device_ops"][0] == ["program:jit_step", pytest.approx(4.0)]
    assert all(name != "while" for name, _ in doc["device_ops"])
    name, seconds = doc["idle_gaps"][0]
    assert seconds == pytest.approx(3.0)
    assert name == "bench.send|paged_attention->all-reduce"
    assert xplane.reduce({"devices": {}, "spans": []}, 1) is None


def test_op_names():
    import xplane
    assert xplane.op_name("%paged_attention.228 = f32[8,1,1280]{2,1,0} "
                          "custom-call(f32[8] %x)") == "paged_attention"
    assert xplane.op_name("jit_pdecode(18337995565266093978)") == "jit_pdecode"
    assert xplane.op_name("%all-reduce-start.3 = (f32[4]) all-reduce-start("
                          "f32[4] %y)") == "all-reduce-start"


def test_manifest_is_consistent_with_the_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert w["name"] == w["config"] + "." + w["traffic"]
    for c in manifest["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads",
                                                               cells))
    # no cell, configuration or metric name in the code
    names = cells | {c["name"] for c in manifest["configs"]} \
        | {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    names -= {"throughput"}          # an English word the docstrings use
    code = [os.path.join(BENCH, f) for f in ("run.py", "sweep.py", "loadgen.py",
                                             "xplane.py")]
    for sub in ("runners", "readers"):
        code += [os.path.join(BENCH, sub, f)
                 for f in os.listdir(os.path.join(BENCH, sub))
                 if f.endswith(".py")]
    import re
    for path in code:
        text = open(path).read()
        assert not [n for n in names if re.search(
            r"(?<![\w.\-])" + re.escape(n) + r"(?![\w\-])", text)], path


def test_manifest_meets_the_contract_limits():
    import re
    text = open(os.path.join(ROOT, "BENCHMARK.json")).read()
    assert len(text.encode()) <= 64 * 1024
    m = json.loads(text)
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

    def line(s):
        return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s

    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert all(line(w) for w in m["command"]) and len(m["command"]) <= 32
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert all(name.match(k) for k in c["reduced"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(name.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) \
        == len(m["workloads"])
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(p["layer"])
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    for x in metrics:
        assert name.match(x["name"]) and unit.match(x["unit"])
        assert x["better"] in ("lower", "higher")
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    for w in m["workloads"]:      # every cell: setup_s, another e2e, a per-layer
        mine = [e["name"] for e in m["end_to_end"]
                if w["name"] in e.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in p.get("workloads", [w["name"]])
                   for p in m["per_layer"])
    for root, _, files in os.walk(BENCH):
        if "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
