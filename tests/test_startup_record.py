"""The start-up record (PR 37): one record a scheduler program MADE, written
where ``ContinuousBatcher._compiled`` makes it (``aot.compile_recorded``),
jax's own compile events attributed to the program being made on the thread
that makes it (``aot.CompileStats.making``), the process marks from import to
ready (``observability.StartupMarks``), and what publishes them:
``ContinuousBatcher.stats()``'s flat ``startup_*`` numbers,
``ClusterServing.warmup_state()["programs"]``, the flight recorder's
``compile`` event and ``serving_warmup_seconds{phase}``.

Everything runs on the CPU on a tiny ``TransformerLM`` through the paged
scheduler — the path the benchmark's cells run.  The test process keeps the
persistent compile cache off (``conftest.py``), so a record's ``cache`` reads
``off`` here; the cache's verdicts are read in a child over a directory of
its own.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.common.observability import (StartupMarks,
                                                    get_recorder,
                                                    get_startup)
from analytics_zoo_tpu.inference import aot
from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                GenerationParams, GenRequest)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEN = dict(paged=True, block_len=4, max_active_slots=2, max_tokens=8,
           eos_id=None, max_prompt_len=16, prefill_buckets=[8, 16],
           bucket_lens=[32], decode_quantum=2, stream_interval=2,
           prefix_cache=False)
SECONDS = ("lower_s", "compile_s", "trace_s", "mlir_s", "backend_s",
           "retrieval_s")
BYTES = ("code_bytes", "alias_bytes")
STAGES = {"lower": "lower_s", "compile": "compile_s", "trace": "trace_s",
          "mlir": "mlir_s", "backend": "backend_s",
          "retrieval": "retrieval_s"}


def _dense(max_batch=4):
    """A predict-plane model: one Dense layer."""
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(4, activation="softmax", input_shape=(3,)))
    m.init_weights()
    return InferenceModel(max_batch=max_batch) \
        .do_load_model(m, m._params, m._state)


def _lm():
    import jax
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    lm = TransformerLM(vocab_size=64, hidden=32, n_head=2, n_layers=1,
                       max_len=32)
    return InferenceModel().do_load_model(
        lm, lm.build(jax.random.PRNGKey(0)), {})


@pytest.fixture(scope="module")
def im():
    return _lm()


def _prompt(n: int, start: int = 1) -> np.ndarray:
    return (np.arange(n, dtype=np.int32) + start) % 63 + 1


def _drive(b: ContinuousBatcher, max_steps: int = 500) -> list:
    events = []
    for _ in range(max_steps):
        events.extend(b.step())
        if b.idle:
            return events
    raise AssertionError("scheduler did not drain")


@pytest.fixture(scope="module")
def warmed(im):
    """A batcher after ``warm()``, with COMPILE_STATS before and after."""
    aot.install_compile_listeners()
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    before = aot.COMPILE_STATS.snapshot()
    doc = b.warm()
    return b, doc, before, aot.COMPILE_STATS.snapshot()


# -- the records ------------------------------------------------------------------

def test_warm_leaves_one_record_a_manifest_entry(warmed):
    b, doc, _, _ = warmed
    manifest = b.warmup_manifest()
    records = b.program_records
    assert doc["compiled"] == len(manifest) == len(records) == 5
    assert b.program_stats()["count"] == len(records)
    assert [r["program"] for r in records] == [
        "paged_decode@32", "paged_prefill:b1xp8", "paged_prefill:b1xp16",
        "paged_prefill:b2xp8", "paged_prefill:b2xp16"]
    for r in records:
        assert r["cause"] == "warmup" and r["cache"] == "off"
        assert set(r) == {"program", "cause", "t", "cache", *SECONDS, *BYTES}
        assert r["lower_s"] > 0 and r["compile_s"] > 0
        assert all(isinstance(r[k], int) and r[k] >= 0 for k in BYTES)
    assert [r["t"] for r in records] == sorted(r["t"] for r in records)
    # what a paged program takes over in place: the decode program the
    # whole pool, the prefill programs nothing (PR 29)
    lane = b._lanes[0]
    assert [r["alias_bytes"] for r in records] \
        == [lane.state_nbytes, 0, 0, 0, 0]


def test_executed_programs_carry_their_record_names(im):
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    b.warm()
    for i, n in enumerate([3, 12]):
        assert b.submit(GenRequest(f"r{i}", _prompt(n, i), max_tokens=4))
    _drive(b)
    ran = set(b.program_stats()["programs"])
    assert ran and ran <= {r["program"] for r in b.program_records}
    assert len(b.program_records) == 5          # nothing was made late


def test_jaxs_seconds_lie_inside_the_walls(warmed):
    """``trace_s`` + ``mlir_s`` within ``lower_s`` and ``backend_s`` within
    ``compile_s``: jax times a nested ``jit`` (``jnp.matmul`` is one) inside
    its caller's event too, and a plain sum of the events would exceed the
    wall it was measured in."""
    for r in warmed[0].program_records:
        assert 0 < r["trace_s"] and 0 < r["mlir_s"]
        assert r["trace_s"] + r["mlir_s"] <= r["lower_s"] + 1e-3
        assert 0 < r["backend_s"] <= r["compile_s"] + 1e-3
        assert r["retrieval_s"] == 0


def test_stats_sums_equal_the_records_and_the_process_counters(warmed):
    b, doc, before, after = warmed
    s, records = b.stats(), b.program_records
    for stage, field in STAGES.items():
        assert s["startup_s." + stage] == pytest.approx(
            sum(r[field] for r in records))
    assert s["startup_n.programs"] == len(records)
    assert s["startup_n.cache_hits"] == s["startup_n.cache_misses"] == 0
    assert s["startup_n.late"] == 0
    assert s["startup_b.code"] == sum(r["code_bytes"] for r in records)
    # nothing else compiled between the two snapshots (rounded to 1 ms each)
    delta = {k: after[k] - before[k] for k in after}
    assert delta["compile_requests"] == len(records)
    assert delta == pytest.approx(doc["compile_stats"], abs=2e-3)
    assert delta["compile_seconds"] == pytest.approx(
        s["startup_s.backend"], abs=2e-3)
    assert delta["cache_hits"] == s["startup_n.cache_hits"]
    assert delta["cache_misses"] == s["startup_n.cache_misses"]
    # every number is flat, as the benchmark's runners snapshot them
    assert all(isinstance(v, (int, float)) for k, v in s.items()
               if k.startswith("startup_"))


def test_a_program_made_for_a_request_is_late(im):
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    b.warm([e for e in b.warmup_manifest() if e.kind == "paged_decode"])
    assert b.stats()["startup_n.late"] == 0
    recorder, seen = get_recorder(), len(get_recorder().events("compile"))
    assert b.submit(GenRequest("r0", _prompt(5), max_tokens=4))
    _drive(b)
    late = [r for r in b.program_records if r["cause"] == "request"]
    assert [r["program"] for r in late] == ["paged_prefill:b1xp8"]
    stats = b.stats()
    assert stats["startup_n.late"] == 1 and stats["startup_n.programs"] == 2
    # the stages' seconds are the warm-up's: the late program's lie inside
    # the stall it caused, and stay in its record
    warmed = [r for r in b.program_records if r["cause"] == "warmup"]
    assert late[0]["lower_s"] > 0 and len(warmed) == 1
    for stage, field in STAGES.items():
        assert stats["startup_s." + stage] == pytest.approx(warmed[0][field])
    assert stats["startup_b.code"] == sum(
        r["code_bytes"] for r in b.program_records)
    event = recorder.events("compile")[seen:][-1]
    assert (event["program"], event["cause"], event["cache"]) \
        == ("paged_prefill:b1xp8", "request", "off")


def test_a_looked_up_program_adds_no_record(warmed):
    b = warmed[0]
    n, requests = len(b.program_records), \
        aot.COMPILE_STATS.snapshot()["compile_requests"]
    lane = b._lanes[0]
    for key in (("pdecode", 32), ("pprefill", 2, 16)):
        assert b._compiled(key, lane) is b._programs[key]
    assert b.warm()["skipped"] == 5
    assert len(b.program_records) == n
    assert aot.COMPILE_STATS.snapshot()["compile_requests"] == requests


def test_two_threads_compiling_at_once_keep_their_events_apart():
    """The warm-up thread and the generate thread may both be inside
    ``lower()`` / ``compile()``: each thread's events go to the program
    that thread is making."""
    import jax
    import jax.numpy as jnp
    aot.install_compile_listeners()

    def heavy(x):
        for _ in range(60):
            x = jnp.tanh(x @ x) + jnp.sin(x)
        return x

    def light(x):
        return x + 1

    jobs = {"heavy": heavy, "light": light}
    x = np.ones((32, 32), np.float32)
    made, errors, gate = {}, [], threading.Barrier(2)
    seen = len(get_recorder().events("compile"))
    before = aot.COMPILE_STATS.snapshot()

    def make(name):
        try:
            gate.wait(timeout=30)
            made[name] = aot.compile_recorded(
                jax.jit(jobs[name]), (x,), name, "warmup")[1]
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    threads = [threading.Thread(target=make, args=(n,)) for n in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    heavy_r, light_r = made["heavy"], made["light"]
    # the two compiles overlapped ...
    assert light_r["t"] < heavy_r["t"] + heavy_r["lower_s"] \
        + heavy_r["compile_s"]
    # ... and each record holds its own program's seconds: within its own
    # walls, and the light program's far under the heavy one's
    for r in (heavy_r, light_r):
        assert r["trace_s"] + r["mlir_s"] <= r["lower_s"] + 1e-3
        assert 0 < r["backend_s"] <= r["compile_s"] + 1e-3
    assert light_r["trace_s"] < heavy_r["trace_s"] / 4
    after = aot.COMPILE_STATS.snapshot()
    assert after["compile_requests"] - before["compile_requests"] == 2
    assert after["compile_seconds"] - before["compile_seconds"] \
        == pytest.approx(heavy_r["backend_s"] + light_r["backend_s"],
                         abs=2e-3)
    events = get_recorder().events("compile")[seen:]
    assert sorted(e["program"] for e in events) == ["heavy", "light"]
    # outside ``making`` an event belongs to no program
    jax.jit(lambda v: v * 3).lower(x).compile()
    assert "program" not in get_recorder().events("compile")[-1]


# -- the marks ----------------------------------------------------------------------

def test_marks_are_set_once_and_an_engine_keeps_its_own():
    marks = StartupMarks()
    t = marks.stamp("imported")
    assert 0 < t <= time.monotonic()
    assert marks.stamp("imported") == t               # the first stamp wins
    assert marks.stamp("imported", t + 5) == t
    # an engine starts from the process's marks and stamps its own alone
    first, second = (StartupMarks(marks.snapshot()) for _ in range(2))
    assert first.get("imported") == t
    assert first.stamp("engine", t + 2) == t + 2
    assert second.stamp("engine", t + 4) == t + 4     # each engine its own
    assert first.stamp("ready", t + 3) == t + 3
    assert marks.snapshot() == {"imported": t}        # nothing flows back
    assert second.get("ready") is None
    first.stamp("model_loaded", t + 1)
    assert list(first.snapshot()) \
        == ["imported", "model_loaded", "engine", "ready"]


def test_the_process_marks_are_there_before_any_engine():
    marks = get_startup().snapshot()
    assert 0 < marks["imported"] <= time.monotonic()
    assert not set(marks) - {"imported", "model_loaded"}
    _lm()
    assert get_startup().get("model_loaded") >= marks["imported"]


# -- the engine's side ----------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(im):
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue
    q = InProcQueue()
    s = ClusterServing(_lm(), q, ServingParams(warmup=True, generation=GEN))
    s.start()
    deadline = time.monotonic() + 120
    while s.warmup_state()["state"] in ("pending", "warming"):
        assert time.monotonic() < deadline, "warm-up never completed"
        time.sleep(0.02)
    yield s, q
    s.shutdown()


def test_a_started_engine_lists_every_warmed_program(engine):
    s, _ = engine
    doc = s.warmup_state()
    assert doc["state"] == "ready" and doc["total"] == 5
    assert [r["program"] for r in doc["programs"]] \
        == [r["program"] for r in s._batcher.program_records]
    assert len(doc["programs"]) == 5
    for r in doc["programs"]:
        assert r["cause"] == "warmup" and r["cache"] in ("hit", "miss", "off")
        assert r["lower_s"] > 0 and r["compile_s"] > 0
        assert all(isinstance(r[k], int) for k in BYTES)
    json.dumps(doc)                               # /readyz serialises it
    assert set(doc["compile_stats"]) == set(aot.COMPILE_STATS.snapshot())
    assert s.health()["warmup"]["programs"] == doc["programs"]
    events = [e for e in get_recorder().events("compile")
              if e.get("cause") == "warmup"]
    assert {r["program"] for r in doc["programs"]} \
        <= {e["program"] for e in events}


def test_the_engines_marks_are_in_order_and_cold_start_reads_them(engine):
    s, _ = engine
    marks = s.startup.snapshot()
    order = list(marks)                     # a snapshot keeps ORDER
    assert [k for k in order if k != "first_result"][-4:] \
        == ["model_loaded", "engine", "warm_begin", "ready"]
    stamps = [marks[k] for k in order]
    assert stamps == sorted(stamps)
    assert s._cold_start_s == pytest.approx(marks["ready"] - marks["engine"])
    assert s.health()["cold_start_s"] == round(s._cold_start_s, 3)
    stats = s._batcher.stats()
    for name, t in marks.items():
        assert stats["startup_t." + name] == t
    # the records lie between the two marks of the pass
    for r in s._batcher.program_records:
        assert marks["warm_begin"] <= r["t"] \
            and r["t"] + r["lower_s"] + r["compile_s"] <= marks["ready"]


def test_warmup_seconds_gain_their_phases(engine):
    s, _ = engine
    prom = s.prom_metrics()
    for phase in ("compile", "init", "lower", "backend", "retrieval"):
        assert f'serving_warmup_seconds{{phase="{phase}"}}' in prom
    gauge = s.registry.get("serving_warmup_seconds")
    totals = aot.startup_totals(s._batcher.program_records)
    marks = s.startup.snapshot()
    assert gauge.labels(phase="lower").value \
        == pytest.approx(totals["startup_s.lower"])
    assert gauge.labels(phase="backend").value \
        == pytest.approx(totals["startup_s.backend"])
    assert gauge.labels(phase="init").value \
        == pytest.approx(marks["warm_begin"] - marks["engine"])
    # the whole pass holds its programs' two walls
    assert gauge.labels(phase="compile").value + 2e-3 \
        >= totals["startup_s.lower"] + totals["startup_s.compile"]


def test_the_first_result_is_stamped_once_and_after_ready(engine):
    import base64
    s, q = engine
    cold = s._cold_start_s
    for i in range(2):
        arr = np.ascontiguousarray(_prompt(5, i).astype("<f4"))
        q.xadd({"uri": f"u{i}", "b64": base64.b64encode(arr).decode("ascii"),
                "dtype": "<f4", "shape": [5], "gen": {"max_tokens": 4}})
        deadline = time.monotonic() + 60
        while q.get_result(f"u{i}") is None:
            assert time.monotonic() < deadline, "no result"
            time.sleep(0.01)
        # the mark is stamped right behind the write the client sees
        while s.startup.get("first_result") is None:
            assert time.monotonic() < deadline, "no mark"
            time.sleep(0.01)
        if i == 0:
            first = s.startup.get("first_result")
    assert first is not None and s.startup.get("first_result") == first
    assert first >= s.startup.get("ready")
    assert s._cold_start_s == cold          # ready came first: the clock stood
    assert s._batcher.stats()["startup_n.late"] == 0


def test_without_warm_up_the_first_result_stops_the_cold_start_clock():
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue
    q = InProcQueue()
    s = ClusterServing(_dense(), q,
                       params=ServingParams(batch_size=2))
    s.start()
    try:
        assert s._cold_start_s is None and s.warmup_state()["programs"] == []
        uri = InputQueue(q).enqueue_tensor(
            "a", np.random.default_rng(0).random(3).astype(np.float32))
        assert OutputQueue(q).query(uri, timeout_s=30) is not None
        deadline = time.monotonic() + 30    # stamped right behind the write
        while s._cold_start_s is None:
            assert time.monotonic() < deadline, "no mark"
            time.sleep(0.01)
        marks = s.startup.snapshot()
        assert "ready" not in marks and "warm_begin" not in marks
        assert s._cold_start_s == pytest.approx(
            marks["first_result"] - marks["engine"])
    finally:
        s.shutdown()


# -- both planes share one pass ------------------------------------------------------

def test_both_planes_return_the_same_stats_document(warmed):
    predict = aot.warm_up(_dense())
    generate = warmed[1]
    assert set(predict) == set(generate) == {
        "programs", "compiled", "skipped", "failed", "errors", "stopped",
        "seconds", "compile_stats"}
    assert set(predict["compile_stats"]) == set(generate["compile_stats"]) \
        == set(aot.COMPILE_STATS.snapshot())
    # a pass that is told to stop makes nothing
    stopped = aot.warm_pass([1, 2], lambda entry: 1 / 0, stop=lambda: True)
    assert stopped["stopped"] and stopped["failed"] == 0
    failed = aot.warm_pass([1, 2], lambda entry: 1 / 0)
    assert failed["failed"] == 2 and "ZeroDivisionError" in failed["errors"][0]


# -- the persistent cache's verdicts -------------------------------------------------

_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from analytics_zoo_tpu.inference import aot
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.models.textmodels import TransformerLM
from analytics_zoo_tpu.serving.generate import ContinuousBatcher, GenerationParams

aot.enable_persistent_cache(sys.argv[1])
lm = TransformerLM(vocab_size=64, hidden=32, n_head=2, n_layers=1, max_len=32)
im = InferenceModel().do_load_model(lm, lm.build(jax.random.PRNGKey(0)), {})
out = []
for _ in range(2):
    b = ContinuousBatcher(im, GenerationParams(**json.loads(sys.argv[2])))
    b.warm()
    s = b.stats()
    out.append({"stats": {k: v for k, v in s.items() if k.startswith("startup_")},
                "records": b.program_records})
print(json.dumps(out))
"""


@pytest.mark.coldstart
def test_a_second_batcher_over_a_populated_cache_reads_no_miss(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)    # it would win over argv[1]
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(tmp_path / "cache"),
         json.dumps(GEN)],
        capture_output=True, text=True, env=env, timeout=240, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    cold, warm = json.loads(out.stdout.strip().splitlines()[-1])
    assert cold["stats"]["startup_n.programs"] == 5
    assert cold["stats"]["startup_n.cache_misses"] == 5
    assert cold["stats"]["startup_n.cache_hits"] == 0
    assert [r["cache"] for r in cold["records"]] == ["miss"] * 5
    assert warm["stats"]["startup_n.cache_misses"] == 0
    assert warm["stats"]["startup_n.cache_hits"] \
        == warm["stats"]["startup_n.programs"] == 5
    for r in warm["records"]:
        assert r["cache"] == "hit"
        assert 0 < r["retrieval_s"] <= r["backend_s"] + 1e-3
        assert r["backend_s"] <= r["compile_s"] + 1e-3
    assert warm["stats"]["startup_s.retrieval"] == pytest.approx(
        sum(r["retrieval_s"] for r in warm["records"]))
    assert all(r["retrieval_s"] == 0 for r in cold["records"])
