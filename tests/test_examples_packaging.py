"""Packaging + runnable examples + serving hardening (VERDICT r2 #9).

Examples run as in-process smoke tests (the reference's
run-example-tests*.sh pattern); the pipelined serving loop is exercised
end-to-end through the client queue surface; the Redis queue test is
skip-guarded on a reachable server.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, argv):
    """Run an example's main(argv) in a FRESH subprocess (round 5): the
    examples exercise long in-process train loops, and a native-level crash
    (XLA CPU abort under host oversubscription was observed) must fail ONE
    test, not kill the whole pytest interpreter.  The child returns main()'s
    dict as a tagged JSON line."""
    import json
    import subprocess

    path = os.path.join(REPO, "examples", name)
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import importlib.util, json\n"
        f"spec = importlib.util.spec_from_file_location('example', {path!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        f"out = mod.main({argv!r})\n"
        "print('EXAMPLE_JSON:' + json.dumps(out, default=float))\n")
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    # eight device threads on a host the suite's other workers saturate
    # can take longer than XLA's 40 s to meet in an all-reduce, and XLA
    # then aborts the process (seen in PRs 29 and 30): give them time
    env["XLA_FLAGS"] = flags \
        + " --xla_cpu_collective_call_terminate_timeout_seconds=600"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=900)
    assert r.returncode == 0, (f"example {name} failed:\n"
                               f"stdout:\n{r.stdout[-1500:]}\n"
                               f"stderr:\n{r.stderr[-2500:]}")
    for line in reversed(r.stdout.strip().splitlines()):
        if line.startswith("EXAMPLE_JSON:"):
            return json.loads(line[len("EXAMPLE_JSON:"):])
    raise AssertionError(f"example {name} produced no result line:\n"
                         f"{r.stdout[-2000:]}")


def test_ncf_example_quick():
    out = _run_example("ncf_train.py", ["--quick"])
    assert out["hr_at_10"] > 0.15          # well above untrained baseline
    assert out["eval_users"] == 400


def test_serving_roundtrip_example():
    out = _run_example("serving_roundtrip.py", ["--n", "32"])
    assert out["ok"] and out["completed"] == 32


def test_image_classification_example_quick():
    out = _run_example("image_classification.py", ["--quick"])
    assert out["predict_shape"] == [64, 4]
    assert np.isfinite(out["train_accuracy"])


def test_pipelined_serving_overlaps_and_backpressures(ctx):
    """start() runs a preprocess thread + predict thread with a bounded
    staging buffer; results must flow and the buffer must never exceed
    pipeline_depth."""
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue

    model = Sequential()
    model.add(Dense(4, input_shape=(3,), activation="softmax"))
    model.init_weights()
    im = InferenceModel().do_load_model(model, model._params, model._state)
    q = InProcQueue()
    serving = ClusterServing(im, q, params=ServingParams(
        batch_size=4, pipeline_depth=2))
    serving.start()
    assert serving._pre_thread.is_alive() and serving._thread.is_alive()

    cin, cout = InputQueue(q), OutputQueue(q)
    g = np.random.default_rng(0)
    ids = [cin.enqueue_tensor(f"u{i}", g.normal(size=(3,)).astype(np.float32))
           for i in range(40)]
    got = {}
    deadline = time.time() + 20
    while len(got) < len(ids) and time.time() < deadline:
        for rid in ids:
            if rid not in got:
                r = cout.query(rid)
                if r is not None:
                    got[rid] = r
        time.sleep(0.01)
    serving.shutdown()
    assert len(got) == len(ids)
    assert serving._staged.maxsize == 2


def test_result_write_retries_with_backoff(ctx):
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue

    model = Sequential()
    model.add(Dense(2, input_shape=(3,), activation="softmax"))
    model.init_weights()
    im = InferenceModel().do_load_model(model, model._params, model._state)

    class Flaky(InProcQueue):
        # transient result-store outage on the write hot path: the engine
        # writes through the batched put_results (PR 3) and falls back to
        # per-record put_result, so both draw from one failure budget
        def __init__(self):
            super().__init__()
            self.failures = 3

        def _maybe_fail(self):
            if self.failures > 0:
                self.failures -= 1
                raise ConnectionError("redis OOM")   # ClusterServing.scala:276

        def put_results(self, pairs):
            self._maybe_fail()
            return super().put_results(pairs)

        def put_result(self, key, value):
            self._maybe_fail()
            return super().put_result(key, value)

    q = Flaky()
    serving = ClusterServing(im, q, params=ServingParams(
        batch_size=2, write_retries=5, write_backoff_s=0.001))
    q.xadd({"uri": "a", "data": [1.0, 2.0, 3.0], "shape": [3]})
    assert serving.serve_once() == 1
    assert q.failures == 0                      # retried through the failures
    assert q.get_result("1") is not None or q.result_count() == 1

    # exhausted retries no longer kill the worker (PR 1 resilience): the
    # record is quarantined to the dead-letter channel with a visible error
    # result instead of the exception escaping the serve loop
    q2 = Flaky()
    q2.failures = 99
    serving2 = ClusterServing(im, q2, params=ServingParams(
        batch_size=2, write_retries=2, write_backoff_s=0.001))
    q2.xadd({"uri": "b", "data": [1.0, 2.0, 3.0], "shape": [3]})
    assert serving2.serve_once() == 0
    dead = q2.dead_letters()
    assert [d["uri"] for d in dead] == ["b"]
    assert "error" in q2.get_result("b")


def _redis_available():
    try:
        import redis
        r = redis.Redis(socket_connect_timeout=0.3)
        r.ping()
        return True
    except Exception:
        return False


@pytest.mark.skipif(not _redis_available(),
                    reason="no reachable redis server")
def test_redis_queue_roundtrip(ctx):
    from analytics_zoo_tpu.serving.queues import RedisQueue

    q = RedisQueue(stream=f"zoo_test_{os.getpid()}")
    rid = q.xadd({"uri": "x", "data": [1.0], "shape": [1]})
    batch = q.read_batch(4, timeout_s=1.0)
    assert any(r == rid for r, _ in batch)
    q.put_result(rid, {"value": [[0, 1.0]]})
    assert q.get_result(rid)["value"] == [[0, 1.0]]


def test_editable_install_metadata():
    """pyproject.toml produces an installable distribution
    (pip install -e . executed during the build; skip when absent)."""
    try:
        import importlib.metadata as md
        version = md.version("analytics-zoo-tpu")
    except Exception:
        pytest.skip("analytics-zoo-tpu not pip-installed in this env")
    assert version == "0.3.0"
