"""Zero-cold-start replicas (PR 11): AOT warm-up manifest + executable
cache, persistent XLA compilation cache across replica spawns, mmap'd
weight store, and the warm-up observability surface (readyz / health /
fleet / manager status)."""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from analytics_zoo_tpu.inference import aot, weightstore
from analytics_zoo_tpu.inference.inference_model import InferenceModel


def _dense_model(out=4, inp=3):
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(out, activation="softmax", input_shape=(inp,)))
    m.init_weights()
    return m


def _loaded(max_batch=16, inp=3):
    m = _dense_model(inp=inp)
    return InferenceModel(max_batch=max_batch) \
        .do_load_model(m, m._params, m._state)


# -- warm-up manifest (satellite: golden derivation) ---------------------------

def test_bucket_ladder_pow2():
    assert aot.bucket_ladder(16) == [1, 2, 4, 8, 16]
    assert aot.bucket_ladder(1) == [1]
    # engine ceiling below the model cap: ladder stops at the ceiling
    assert aot.bucket_ladder(8, model_cap=64) == [1, 2, 4, 8]


def test_bucket_ladder_mesh_multiple():
    # PR 6 mesh-aware buckets: every bucket rounds UP to a multiple of the
    # data-axis size, so the ladder collapses below the multiple
    assert aot.bucket_ladder(16, multiple=4) == [4, 8, 16]
    assert aot.bucket_ladder(8, multiple=8) == [8]


def test_manifest_golden_plain():
    im = _loaded(max_batch=8)
    entries = aot.warmup_manifest(im)
    # shape inferred from the topology's declared input shape; scales
    # "auto" doubles every bucket with the int8 per-row-scale variant
    assert [(e.bucket, e.dtype, e.scales) for e in entries] == [
        (1, "<f4", False), (1, "|i1", True),
        (2, "<f4", False), (2, "|i1", True),
        (4, "<f4", False), (4, "|i1", True),
        (8, "<f4", False), (8, "|i1", True)]
    assert all(e.shape == (3,) and e.mesh is None and e.sharding == "off"
               for e in entries)


def test_manifest_non_pow2_clamp():
    # a non-pow-2 max_batch is clamped DOWN at model construction (PR 6);
    # the manifest must reflect the clamped ladder, not the raw value
    im = _loaded(max_batch=100)          # clamps to 64
    buckets = sorted({e.bucket for e in aot.warmup_manifest(im)})
    assert buckets == [1, 2, 4, 8, 16, 32, 64]


def test_manifest_sharded_mesh_multiple():
    # sharded placement in force: buckets round to the data-axis multiple
    # and the entries record the mesh/sharding they were derived against
    im = _loaded(max_batch=16).shard(mesh=4, sharding="batch")
    entries = aot.warmup_manifest(im)
    assert sorted({e.bucket for e in entries}) == [4, 8, 16]
    assert all(e.mesh == (4, 1) and e.sharding == "batch"
               for e in entries)


def test_manifest_spec_overrides():
    im = _loaded(max_batch=16)
    entries = aot.resolve_manifest(
        im, {"shape": [5], "max_batch": 4, "scales": "off"})
    assert [(e.bucket, e.shape, e.scales) for e in entries] == [
        (1, (5,), False), (2, (5,), False), (4, (5,), False)]


def test_manifest_u8_scale_dtype():
    # a u8-image deployment (QuantizedTensor(uint8, 1.0) records) warms
    # its per-row-scale program via the spec's scale_dtypes — the default
    # int8 wire alone would leave the ("|u1", scales) program cold
    im = _loaded(max_batch=4)
    entries = aot.resolve_manifest(
        im, {"scale_dtypes": ["|i1", "|u1"], "max_batch": 2})
    assert [(e.bucket, e.dtype, e.scales) for e in entries] == [
        (1, "<f4", False), (1, "|i1", True), (1, "|u1", True),
        (2, "<f4", False), (2, "|i1", True), (2, "|u1", True)]
    stats = aot.warm_up(im, entries)
    assert stats["failed"] == 0
    # the warmed u8 program serves without a fresh compile
    compiles = im.aot_stats()["compiles"]
    im.do_predict(np.ones((2, 3), np.uint8),
                  scales=np.ones(2, np.float32))
    assert im.aot_stats()["compiles"] == compiles


def test_manifest_underivable_raises():
    m = _dense_model()
    m._declared_input_shape = None
    im = InferenceModel(max_batch=4).do_load_model(m, m._params, m._state)
    with pytest.raises(ValueError):
        aot.warmup_manifest(im)


# -- AOT executable cache ------------------------------------------------------

def test_warmup_then_serve_without_retrace():
    im = _loaded(max_batch=8)
    stats = aot.warm_up(im, aot.resolve_manifest(im, True))
    assert stats["programs"] == 8 and stats["failed"] == 0
    compiles_after_warm = im.aot_stats()["compiles"]
    assert compiles_after_warm == 8
    g = np.random.default_rng(0)
    # every size the engine can produce, f32 and int8-wire: all hits
    for n in (1, 2, 3, 5, 8):
        im.do_predict(g.random((n, 3), np.float32))
        im.dispatch(g.random((n, 3), np.float32)).result()
        im.do_predict((g.random((n, 3)) * 10).astype(np.int8),
                      scales=np.ones(n, np.float32))
    post = im.aot_stats()
    assert post["compiles"] == compiles_after_warm, \
        "a warmed bucket was re-compiled"
    assert post["hits"] >= 15


def test_warm_up_skips_cached_entries():
    im = _loaded(max_batch=4)
    first = aot.warm_up(im, aot.resolve_manifest(im, True))
    again = aot.warm_up(im, aot.resolve_manifest(im, True))
    assert first["compiled"] == first["programs"]
    assert again["compiled"] == 0
    assert again["skipped"] == again["programs"]


def test_reload_invalidates_aot_cache():
    im = _loaded(max_batch=4)
    aot.warm_up(im, aot.resolve_manifest(im, True))
    epoch = im.aot_stats()["epoch"]
    m2 = _dense_model()
    im.do_load_model(m2, m2._params, m2._state)
    post = im.aot_stats()
    assert post["epoch"] == epoch + 1
    assert post["cached_programs"] == 0


def test_scaled_wrapper_survives_base_flip():
    """Satellite regression: the scaled program is cached per BASE, so a
    base that drifts A -> B -> A (instance patches, chaos shims) re-uses
    A's wrapper and its compiled buckets — interleaved scaled/unscaled
    dispatches never rebuild what they already paid for."""
    im = _loaded(max_batch=8)
    g = np.random.default_rng(0)
    x8 = (g.random((4, 3)) * 10).astype(np.int8)
    xf = g.random((4, 3), np.float32)
    sc = np.ones(4, np.float32)
    im.dispatch(x8, scales=sc).result()
    im.dispatch(xf).result()
    base_compiles = im.aot_stats()["compiles"]
    assert base_compiles == 2             # one program per variant
    # interleave: no rebuilds, no recompiles
    for _ in range(5):
        im.dispatch(x8, scales=sc).result()
        im.dispatch(xf).result()
    assert im.aot_stats()["compiles"] == base_compiles
    wrapper_a = im._jitted_with_scales()
    # drift A -> B (a different program) and back to A: B compiles its
    # own bucket, A's executables are NOT invalidated by the round-trip
    orig = im._jitted
    import jax
    im._jitted = jax.jit(lambda p, s, x: orig(p, s, x) * 1.0)
    im.dispatch(x8, scales=sc).result()
    drift_compiles = im.aot_stats()["compiles"]
    assert drift_compiles == base_compiles + 1
    im._jitted = orig
    assert im._jitted_with_scales() is wrapper_a
    im.dispatch(x8, scales=sc).result()
    im.dispatch(xf).result()
    assert im.aot_stats()["compiles"] == drift_compiles, \
        "returning to a previously-seen base must hit its cached programs"


def test_patched_jitted_never_served_stale():
    """The AOT key carries the program identity: patching `_jitted`
    without an epoch bump must MISS (compile the new program), never
    serve the old executable under the same shape."""
    im = _loaded(max_batch=4)
    x = np.ones((2, 3), np.float32)
    out_a = im.dispatch(x).result()
    import jax
    im._jitted = jax.jit(lambda p, s, xx: jax.numpy.zeros((xx.shape[0], 4)))
    out_b = im.dispatch(x).result()
    assert not np.allclose(out_a, out_b)
    assert np.allclose(out_b, 0.0)


# -- mmap weight store ---------------------------------------------------------

def test_weight_store_roundtrip_mmap(tmp_path):
    m = _dense_model()
    store = str(tmp_path / "store")
    manifest = weightstore.save_store(
        store, {"params": m._params, "state": m._state})
    assert manifest["leaves"] and not manifest.get("skipped")
    # idempotent re-export: fingerprint match skips the rewrite
    again = weightstore.save_store(
        store, {"params": m._params, "state": m._state})
    assert again.get("skipped") is True
    flat = weightstore.load_flat(store)
    assert all(isinstance(v, np.memmap) for v in flat.values())
    like = {"params": m._params, "state": m._state}
    tree = weightstore.load_store(store, like=like)
    import jax
    flat_a = jax.tree_util.tree_leaves(tree["params"])
    flat_b = jax.tree_util.tree_leaves(m._params)
    assert all(np.array_equal(x, np.asarray(y))
               for x, y in zip(flat_a, flat_b))


def test_do_load_store_predicts_identically(tmp_path):
    def build():
        from analytics_zoo_tpu.nn import Sequential
        from analytics_zoo_tpu.nn.layers import Dense
        m = Sequential()
        m.add(Dense(4, activation="softmax", input_shape=(3,)))
        return m

    m = build()
    m.init_weights()
    ref = InferenceModel(max_batch=8).do_load_model(m, m._params, m._state)
    store = str(tmp_path / "store")
    weightstore.save_store(store, {"params": m._params, "state": m._state})
    # do_load routes a directory to the mmap store path
    im = InferenceModel(max_batch=8).do_load(build, store)
    assert im.load_mmap and im.load_seconds is not None
    x = np.random.default_rng(0).random((5, 3)).astype(np.float32)
    assert np.allclose(ref.do_predict(x), im.do_predict(x))


def test_weight_store_shape_mismatch_rejected(tmp_path):
    m = _dense_model()
    store = str(tmp_path / "store")
    weightstore.save_store(store, {"params": m._params, "state": m._state})
    big = _dense_model(out=7)
    with pytest.raises(KeyError):
        weightstore.load_store(
            store, like={"params": big._params, "state": big._state})


# -- engine integration: warming readiness + cold-start metrics ----------------

@pytest.mark.coldstart
def test_engine_readyz_warming_progress():
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue

    im = _loaded(max_batch=8)
    orig_warm = im.warm

    def slow_warm(*a, **kw):
        time.sleep(0.25)
        return orig_warm(*a, **kw)

    im.warm = slow_warm
    q = InProcQueue()
    s = ClusterServing(im, q, params=ServingParams(
        batch_size=4, warmup=True, http_port=0))
    s.start()
    try:
        import urllib.error
        import urllib.request
        url = f"{s._http.url}/readyz"
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                doc, code = json.loads(resp.read()), resp.status
        except urllib.error.HTTPError as e:
            doc, code = json.loads(e.read()), e.code
        assert code == 503 and not doc["ready"]
        assert any("warming" in r for r in doc["reasons"])
        assert doc["warmup"]["state"] in ("pending", "warming")
        assert doc["warmup"]["total"] == 8
        deadline = time.time() + 60
        while s.warmup_state()["state"] in ("pending", "warming"):
            assert time.time() < deadline, "warm-up never completed"
            time.sleep(0.05)
        with urllib.request.urlopen(url, timeout=5) as resp:
            doc = json.loads(resp.read())
            assert resp.status == 200
        assert doc["ready"] and doc["warmup"]["state"] == "ready"
        # cold start stamped at warm completion, before any traffic
        h = s.health()
        assert h["cold_start_s"] is not None
        assert h["warmup"]["compiled"] == 8
        # ... and read off the engine's start-up marks (PR 37): engine ->
        # ready, with the warm-up pass between its own two marks
        marks = s.startup.snapshot()
        assert h["cold_start_s"] == round(marks["ready"] - marks["engine"], 3)
        assert marks["imported"] <= marks["model_loaded"] <= marks["engine"] \
            <= marks["warm_begin"] <= marks["ready"]
        assert marks["ready"] - marks["warm_begin"] \
            >= h["warmup"]["seconds"] - 0.01
        # the predict plane has no scheduler programs to list
        assert h["warmup"]["programs"] == [] and "programs" not in doc["warmup"]
        # …and serving still works, off the warmed executables
        compiles = im.aot_stats()["compiles"]
        cin, cout = InputQueue(q), OutputQueue(q)
        uri = cin.enqueue_tensor(
            "a", np.random.default_rng(0).random(3).astype(np.float32))
        res = cout.query(uri, timeout_s=30)
        assert res is not None and "value" in res
        assert im.aot_stats()["compiles"] == compiles
        prom = s.prom_metrics()
        assert "replica_cold_start_seconds" in prom
        assert 'serving_warmup_seconds{phase="compile"}' in prom
        assert 'serving_warmup_seconds{phase="init"}' in prom
    finally:
        s.shutdown()


def test_engine_warmup_off_by_default():
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue
    s = ClusterServing(_loaded(max_batch=4), InProcQueue(),
                       params=ServingParams(batch_size=2))
    s.start()
    try:
        assert s.warmup_state()["state"] == "off"
        assert s.ready()["ready"]
        assert "warmup" not in s.ready()
    finally:
        s.shutdown()


def test_engine_warmup_underivable_stays_ready():
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue
    m = _dense_model()
    m._declared_input_shape = None
    im = InferenceModel(max_batch=4).do_load_model(m, m._params, m._state)
    s = ClusterServing(im, InProcQueue(),
                       params=ServingParams(batch_size=2, warmup=True))
    s.start()
    try:
        assert s.warmup_state()["state"] == "off"
        assert s.ready()["ready"]
    finally:
        s.shutdown()


# -- fleet + manager surfacing -------------------------------------------------

def _doc(i, state=None, compiled=0, total=0, cold=None, running=True):
    doc = {"running": running, "replica_id": f"replica-{i}",
           "total_records": 10 * i, "shed": 0, "dead_lettered": 0,
           "reclaimed": 0, "duplicates": 0, "heartbeat_age_s": 0.1,
           "workers": {}, "queue": {"depth": 1, "pending": 0},
           "stages": {"e2e": {"p99_ms": 5.0}},
           "knobs": {"max_batch": 4, "max_batch_ceiling": 16,
                     "inflight_batches": 2, "inflight_ceiling": 4,
                     "preprocess_workers": 1}}
    if state is not None:
        doc["warmup"] = {"state": state, "compiled": compiled,
                         "total": total, "seconds": None}
    if cold is not None:
        doc["cold_start_s"] = cold
    return doc


def test_fleet_aggregates_warming_and_cold_start():
    from analytics_zoo_tpu.serving import fleet
    docs = {0: _doc(0, state="ready", compiled=8, total=8, cold=1.5),
            1: _doc(1, state="warming", compiled=3, total=8),
            2: _doc(2, state="pending", total=8, cold=4.25)}
    agg = fleet.aggregate_health(docs)
    assert agg["replicas_warming"] == 2
    assert agg["cold_start_s"] == 4.25
    fm = fleet.fleet_metrics(docs)
    assert fm["replicas"]["warming"] == 2
    assert fm["cold_start_s"] == 4.25
    assert fm["per_replica"]["replica-1"]["warmup"]["state"] == "warming"
    assert fm["per_replica"]["replica-1"]["warmup"]["compiled"] == 3
    assert fm["per_replica"]["replica-0"]["cold_start_s"] == 1.5


def test_fleet_signals_carry_warming():
    from analytics_zoo_tpu.serving import fleet
    from analytics_zoo_tpu.serving.autoscaler import FleetSignals
    docs = {0: _doc(0, state="warming", compiled=1, total=8, cold=2.0)}
    agg = fleet.aggregate_health(docs)
    sig = FleetSignals(replicas_warming=agg["replicas_warming"],
                       cold_start_s=agg["cold_start_s"])
    assert sig.replicas_warming == 1 and sig.cold_start_s == 2.0


def test_autoscaler_actuation_lag():
    """scale_up decision -> fleet at target AND warm: the lag gauge the
    zero-cold-start work exists to shrink."""
    from analytics_zoo_tpu.serving.autoscaler import (Autoscaler,
                                                      AutoscalerParams,
                                                      FleetSignals)

    class FakeFleet:
        def __init__(self):
            self.desired = 1
            self.sig = FleetSignals(replicas=1, desired=1, max_batch=4,
                                    max_batch_ceiling=4)

        def signals(self):
            return self.sig

        def scale_to(self, n):
            self.desired = n

        def retune(self, **kw):
            pass

        def replace(self, rid):
            pass

    fleet = FakeFleet()
    scaler = Autoscaler(fleet, params=AutoscalerParams(
        slo_p99_ms=100.0, min_replicas=1, max_replicas=4,
        dwell_up_s=0.0, knob_dwell_s=1e9))
    # overload: p99 over the high mark -> scale_up fires (dwell 0)
    fleet.sig.e2e_p99_ms = 500.0
    fleet.sig.queue_depth = 100
    scaler.tick(now=10.0)
    assert fleet.desired == 3             # 1 + max_step 2
    assert scaler._pending_scale == (10.0, 3)
    # members up but still warming: lag NOT stamped yet
    fleet.sig = FleetSignals(replicas=3, desired=3, replicas_warming=2,
                             e2e_p99_ms=10.0, max_batch=4,
                             max_batch_ceiling=4)
    scaler.tick(now=12.0)
    assert scaler._pending_scale is not None
    # warm: lag stamps now - decision time
    fleet.sig = FleetSignals(replicas=3, desired=3, replicas_warming=0,
                             e2e_p99_ms=10.0, cold_start_s=3.2,
                             max_batch=4, max_batch_ceiling=4)
    scaler.tick(now=14.5)
    assert scaler._pending_scale is None
    snap = scaler.registry.snapshot()
    assert snap["autoscaler_actuation_lag_seconds"]["values"][0]["value"] == 4.5


def test_manager_status_surfaces_warmup(tmp_path, capsys):
    from analytics_zoo_tpu.serving import manager
    pidfile = str(tmp_path / "serving.pid")
    # a "running" supervisor (our own pid is alive) with 2 replica slots
    with open(pidfile, "w") as f:
        f.write(str(os.getpid()))
    with open(pidfile + ".replicas", "w") as f:
        f.write("2")
    for i, state in ((0, "ready"), (1, "warming")):
        with open(f"{pidfile}.r{i}", "w") as f:
            f.write(str(os.getpid()))
        doc = _doc(i, state=state, compiled=8 if state == "ready" else 2,
                   total=8, cold=2.5 if state == "ready" else None)
        doc["ready"] = {"ready": state == "ready", "reasons": []}
        with open(f"{pidfile}.r{i}.health.json", "w") as f:
            json.dump(doc, f)
    rc = manager.main(["status", "--pidfile", pidfile,
                       "-c", str(tmp_path / "none.yaml")])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    reps = out["replicas"]
    assert reps["warming"] == 1
    assert reps["members"]["r0"]["warmup"]["state"] == "ready"
    assert reps["members"]["r0"]["cold_start_s"] == 2.5
    assert reps["members"]["r0"]["ready"] is True
    assert reps["members"]["r1"]["warmup"]["compiled"] == 2
    assert reps["members"]["r1"]["ready"] is False


# -- the zero-compile acceptance: spawn twice, second boot never compiles ------

_CHILD = r"""
import json, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from analytics_zoo_tpu.inference import aot
from analytics_zoo_tpu.inference.inference_model import InferenceModel
aot.enable_persistent_cache(sys.argv[1])
from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn.layers import Dense
m = Sequential(); m.add(Dense(4, activation="softmax", input_shape=(3,)))
m.init_weights()
im = InferenceModel(max_batch=8).do_load_model(m, m._params, m._state)
stats = aot.warm_up(im, aot.resolve_manifest(im, True))
out = im.do_predict(np.ones((3, 3), np.float32))
assert out.shape == (3, 4)
print(json.dumps(dict(stats["compile_stats"], programs=stats["programs"],
                      failed=stats["failed"])))
"""


@pytest.mark.coldstart
def test_spawn_twice_second_replica_zero_compiles(tmp_path):
    """The tentpole acceptance: with a shared persistent cache,
    the SECOND replica of a topology performs zero XLA compiles — every
    program of the warm-up set (and the incidental jits around it) loads
    from the cache."""
    cache = str(tmp_path / "xla_cache")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)           # identical topology both spawns
    # the empty tmp directory IS the cold arm: the environment's cache
    # would win over it (aot.compile_cache_dir) and make it warm
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    docs = []
    for spawn in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, cache],
            capture_output=True, text=True, env=env, timeout=240,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        docs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = docs
    assert first["failed"] == 0 and second["failed"] == 0
    assert first["cache_misses"] > 0     # the cold spawn really compiled
    assert first["cache_hits"] == 0
    # the whole point of the PR:
    assert second["cache_misses"] == 0, \
        f"second replica compiled: {second}"
    assert second["cache_hits"] >= second["programs"]


@pytest.mark.coldstart
@pytest.mark.slow
def test_bench_cold_start_ab(tmp_path):
    """serving_bench --cold-start end to end (slow: two interpreter
    spawns + real compiles).  Structural asserts only — no wall-clock
    claim."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import serving_bench
    out = serving_bench.main(["--cold-start", "--cold-max-batch", "8",
                              "--json", str(tmp_path / "ab.json")])
    assert out["warm_zero_compiles"]
    assert out["warm"]["load_mmap"]
    assert out["cold"]["compile_cache_misses"] > 0
    assert out["cold_start_seconds"] is not None
    assert out["compile_cache_hits"] > 0
    doc = json.loads((tmp_path / "ab.json").read_text())
    assert doc["results"][0]["cold_start_seconds"] is not None
