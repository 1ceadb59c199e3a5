"""Sharded multi-chip serving (PR 6): pjit predict over the ICI mesh.

In-process tests run on the conftest 8-device virtual CPU mesh (a 4-device
sub-mesh where the ISSUE specifies 4); the `multichip` test self-spawns
`sharded_worker.py` under XLA_FLAGS=--xla_force_host_platform_device_count=4
so the mesh path is exercised exactly the way a CPU-only container would
run it."""

import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.inference.inference_model import InferenceModel, _bucket
from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn.layers import Dense
from analytics_zoo_tpu.parallel.sharding import (ShardingPlan, serving_mesh,
                                                 serving_mode_for,
                                                 serving_plan)
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
from analytics_zoo_tpu.serving.queues import InProcQueue


def _mlp(dim=4, classes=3):
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(dim,), name="shfc1"))
    m.add(Dense(classes, activation="softmax", name="shfc2"))
    m.init_weights()
    return m


# -- satellite: pow-2 bucket ladder stays pow-2 -------------------------------

def test_max_batch_clamped_to_pow2(caplog):
    """A non-pow-2 max_batch (e.g. 100) used to yield a non-pow-2 TERMINAL
    bucket (100 after 64), doubling the compile cache; it is now clamped
    down with a warning."""
    with caplog.at_level(logging.WARNING):
        im = InferenceModel(max_batch=100)
    assert im.max_batch == 64
    assert any("max_batch=100" in r.message for r in caplog.records)
    # the ladder for the clamped model is pure pow-2
    assert {_bucket(n, im.max_batch) for n in (1, 3, 64, 100, 5000)} \
        == {1, 4, 64}
    # pow-2 values pass through silently
    assert InferenceModel(max_batch=256).max_batch == 256


def test_bucket_mesh_multiple():
    """Mesh-aware bucketing: buckets round UP to a multiple of the batch
    axis so every device gets an equal slice, and stay pow-2 when max_batch
    and the axis are pow-2."""
    assert _bucket(1, 1024, 4) == 4          # below the axis: one row/device
    assert _bucket(3, 1024, 4) == 4
    assert _bucket(5, 1024, 4) == 8          # pow-2 ladder unchanged above
    assert _bucket(100, 1024, 4) == 128
    assert _bucket(3, 2, 4) == 4             # max_batch < axis: axis wins
    assert _bucket(7, 1024, 1) == 8          # single-chip unchanged


def test_shard_indivisible_data_axis(ctx, caplog):
    """An EXPLICIT (data, model) layout whose data axis can't divide the
    pow-2 max_batch is rejected with an attainable fix; an auto-built mesh
    clamps to the largest usable pow-2 batch axis instead of refusing."""
    im = InferenceModel(max_batch=4).do_load_model(_mlp())
    with pytest.raises(ValueError, match="power-of-2 data axis"):
        im.shard(mesh=(8, 1), sharding="batch")
    with caplog.at_level(logging.WARNING):
        im.shard(mesh=8, sharding="batch")   # auto-built: clamp, don't fail
    assert im.mesh_info()["axes"]["data"] == 4
    assert any("largest usable" in r.message for r in caplog.records)
    # a non-pow-2 device count (e.g. 3 visible chips) clamps the same way
    im3 = InferenceModel().do_load_model(_mlp())
    im3.shard(mesh=3, sharding="batch")
    assert im3.mesh_info()["axes"]["data"] == 2


# -- satellite: _fit divisibility fallback ------------------------------------

def test_fit_fallback_replicates_with_one_warning(caplog):
    """A leaf whose dim doesn't divide the mesh axis falls back to
    replication for THAT dimension (pjit never sees the bad spec), and the
    warning fires once per leaf, not once per placement."""
    mesh = serving_mesh(4, mode="tensor")
    plan = ShardingPlan([(r".*W$", P(None, "model"))])
    tree = {"fc": {"W": np.ones((8, 10), np.float32)}}   # 10 % 4 != 0
    with caplog.at_level(logging.WARNING,
                         logger="analytics_zoo_tpu.parallel.sharding"):
        placed = plan.shard(tree, mesh)
        assert placed["fc"]["W"].sharding.spec in (P(), P(None, None))
        plan.shard(tree, mesh)               # second placement: no new warn
    warns = [r for r in caplog.records if "not divisible" in r.message]
    assert len(warns) == 1
    # a dividing leaf under the same plan still shards
    ok = plan.shard({"fc": {"W": np.ones((8, 12), np.float32)}}, mesh)
    assert ok["fc"]["W"].sharding.spec == P(None, "model")


# -- serving_plan selector ----------------------------------------------------

def test_serving_plan_selector():
    transformer_ish = {
        "blk0_attn": {"qkv": {"W": np.zeros((8, 24), np.float32)},
                      "out": {"W": np.zeros((8, 8), np.float32)}},
        "blk0_ffn": {"fc": {"W": np.zeros((8, 32), np.float32)},
                     "proj": {"W": np.zeros((32, 8), np.float32)}}}
    flat = {"emb": {"table": np.zeros((16, 8), np.float32)}}
    # auto-mode heuristic: size gates tensor parallelism
    assert serving_mode_for(transformer_ish, min_tensor_params=10**9) \
        == "batch"
    assert serving_mode_for(transformer_ish, min_tensor_params=1) == "tensor"
    # structure gates it too: nothing megatron-shardable -> batch even if big
    assert serving_mode_for(flat, min_tensor_params=1) == "batch"
    # plan selection over a mesh with a model axis
    tmesh = serving_mesh(4, mode="tensor")
    assert serving_plan(transformer_ish, tmesh,
                        min_tensor_params=1).rules    # megatron (has rules)
    assert not serving_plan(flat, tmesh, min_tensor_params=1).rules
    # batch-mode mesh (model axis 1) always replicates params
    bmesh = serving_mesh(4, mode="batch")
    assert not serving_plan(transformer_ish, bmesh,
                            min_tensor_params=1).rules


# -- numerical equivalence (simulated 4-device mesh) --------------------------

def test_sharded_do_predict_bitwise_f32(ctx):
    """Batch-sharded predict == single-chip predict BITWISE for f32 (each
    row's math runs whole on one device), including a padded final bucket
    and the chunked multi-bucket path."""
    model = _mlp(dim=6, classes=5)
    x = np.random.default_rng(0).normal(size=(37, 6)).astype(np.float32)
    single = InferenceModel().do_load_model(model)
    sharded = InferenceModel().do_load_model(model)
    sharded.shard(mesh=4, sharding="batch")
    assert sharded.mesh_info()["devices"] == 4
    y1 = single.do_predict(x, batch_size=16)     # chunks 16,16,5 -> pad 8
    y2 = sharded.do_predict(x, batch_size=16)
    assert np.array_equal(y1, y2)
    # dispatch handle (the serving hot path) pads 11 -> 16 and still matches
    assert np.array_equal(single.do_predict(x[:11]),
                          sharded.dispatch(x[:11]).result())


def test_sharded_int8_wire_within_tolerance(ctx):
    """int8-wire records through the sharded path (rows AND per-row scales
    split over the batch axis) match the host-dequantized f32 reference."""
    model = _mlp(dim=6, classes=5)
    g = np.random.default_rng(2)
    q = g.integers(-127, 127, (9, 6)).astype(np.int8)
    sc = g.uniform(0.01, 0.1, (9,)).astype(np.float32)
    single = InferenceModel().do_load_model(model)
    sharded = InferenceModel().do_load_model(model)
    sharded.shard(mesh=4, sharding="batch")
    got = sharded.do_predict(q, scales=sc)
    want = single.do_predict(q.astype(np.float32) * sc[:, None])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_tensor_sharded_transformer_within_tolerance(ctx):
    """Explicit tensor mode megatron-shards the transformer blocks; the
    cross-chip partial-sum order differs, so tolerance rather than bitwise."""
    from analytics_zoo_tpu.nn.layers.attention import TransformerLayer
    t = TransformerLayer(vocab=64, hidden_size=32, n_block=2, n_head=2,
                         seq_len=8, embedding_drop=0.0, attn_drop=0.0,
                         resid_drop=0.0)
    params, state = t.init(jax.random.PRNGKey(0), (8,))
    ids = np.random.default_rng(1).integers(0, 64, (6, 8)) \
        .astype(np.float32)
    single = InferenceModel().do_load_model(t, params, state)
    sharded = InferenceModel().do_load_model(t, params, state)
    sharded.shard(mesh=4, sharding="tensor")
    info = sharded.mesh_info()
    assert info["sharding"] == "tensor" and info["axes"]["model"] == 4
    # the qkv/out/ffn weights actually live split over the model axis
    split = [l for l in jax.tree_util.tree_leaves(sharded._params)
             if any(a is not None for a in getattr(l.sharding, "spec", ()))]
    assert split, "tensor mode placed no sharded leaves"
    np.testing.assert_allclose(sharded.do_predict(ids),
                               single.do_predict(ids),
                               rtol=2e-4, atol=2e-5)


def test_shard_idempotent_and_bridge_rejected(ctx, caplog):
    im = InferenceModel().do_load_model(_mlp())
    im.shard(mesh=4, sharding="batch")
    mesh = im._mesh
    with caplog.at_level(logging.WARNING):
        im.shard(mesh=8, sharding="auto")        # no-op: placement sticks
    assert im._mesh is mesh
    # ...but a CONFLICTING topology is called out, not silently swallowed
    assert any("conflicting mesh" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING):
        im.shard(mesh=4, sharding="auto")        # matching request: silent
    assert not any("conflicting" in r.message for r in caplog.records)
    # bridge predict fns (no jit .lower) cannot be partitioned
    bridge = InferenceModel()
    bridge._jitted = lambda p, s, x: x
    bridge._params = {}
    with pytest.raises(ValueError, match="jax-native"):
        bridge.shard(mesh=4, sharding="batch")


def test_explicit_batch_mode_never_tensor_shards(ctx, caplog):
    """sharding=\"batch\" is a contract: params stay replicated even on a
    model the auto heuristic would megatron-shard; sharding=\"tensor\" on a
    model with nothing megatron-matchable warns and replicates."""
    from analytics_zoo_tpu.nn.layers.attention import TransformerLayer
    t = TransformerLayer(vocab=64, hidden_size=32, n_block=1, n_head=2,
                         seq_len=8, embedding_drop=0.0, attn_drop=0.0,
                         resid_drop=0.0)
    params, state = t.init(jax.random.PRNGKey(0), (8,))
    im = InferenceModel().do_load_model(t, params, state)
    im.shard(mesh=4, sharding="batch")
    assert not im._plan.rules                    # replicated, not megatron
    assert all(not any(a is not None for a in getattr(l.sharding, "spec", ()))
               for l in jax.tree_util.tree_leaves(im._params))
    # tensor on a megatron-blind tree: warn + replicate, don't lie
    flat = Sequential()
    flat.add(Dense(3, activation="softmax", input_shape=(4,), name="emb_x"))
    flat.init_weights()
    im2 = InferenceModel().do_load_model(flat)
    # rename-proof: build a params tree with no fc/qkv/proj-style leaf names
    im2._params = {"table": {"T": np.asarray(
        np.random.default_rng(0).normal(size=(8, 4)), np.float32)}}
    with caplog.at_level(logging.WARNING):
        im2.shard(mesh=4, sharding="tensor")
    assert any("no parameter leaf matches" in r.message
               for r in caplog.records)


# -- engine contracts with sharding=auto --------------------------------------

def test_engine_sharded_auto_end_to_end_with_quarantine(ctx):
    """The PR 1-5 pipeline contracts survive the sharded predict: results
    match the single-chip engine's, a poisoned record quarantines alone,
    and drain flushes the dispatched in-flight work.  Same classes, float-
    equal scores: the two engines coalesce the ten records into batches as
    their timing has it, and a row's float32 score moves by an ulp with
    the batch it was computed in.  (The bitwise claim is the model's, where
    the batch is fixed: ``test_sharded_do_predict_bitwise_f32``.)"""
    model = _mlp(dim=4, classes=3)
    xs = [np.random.default_rng(i).normal(size=(4,)).astype(np.float32)
          for i in range(10)]

    def run(sharding):
        q = InProcQueue()
        im = InferenceModel().do_load_model(model)
        s = ClusterServing(im, q, ServingParams(
            batch_size=4, sharding=sharding,
            mesh_shape=4 if sharding != "off" else None)).start()
        cin, cout = InputQueue(q), OutputQueue(q)
        uris = [cin.enqueue_tensor(f"r{i}", x) for i, x in enumerate(xs)]
        q.xadd({"uri": "poison", "b64": "!!!not-base64!!!", "dtype": "<f4"})
        got = cout.query_many(uris + ["poison"], timeout_s=60)
        s.shutdown(drain_s=10)
        return got, s, im

    got_off, _, _ = run("off")
    got_auto, s, im = run("auto")
    assert im.mesh_info()["devices"] == 4
    assert im.mesh_info()["sharded_calls"] > 0
    assert OutputQueue.is_error(got_auto["poison"])     # quarantined alone
    for u in (f"r{i}" for i in range(10)):
        auto, off = got_auto[u]["value"], got_off[u]["value"]
        assert [c for c, _ in auto] == [c for c, _ in off]
        assert [p for _, p in auto] == pytest.approx([p for _, p in off],
                                                     rel=1e-5)
    assert s.dead_lettered == 1 and s.total_records == 10


def test_sharded_metrics_surface(ctx):
    """inference_mesh_devices gauge + the sharding label on
    inference_predict_seconds land in the engine registry's exposition."""
    q = InProcQueue()
    im = InferenceModel().do_load_model(_mlp())
    s = ClusterServing(im, q, ServingParams(
        batch_size=4, sharding="batch", mesh_shape=4))
    InputQueue(q).enqueue_tensor("m0", np.ones(4, np.float32))
    s.serve_once()
    prom = s.prom_metrics()
    assert "inference_mesh_devices 4" in prom
    assert 'sharding="batch"' in prom
    assert s.registry.gauge("inference_mesh_devices").value == 4.0
    s.shutdown()


# -- CPU-only container path: self-spawned 4-device mesh ----------------------

@pytest.mark.multichip
def test_multichip_worker_equivalence_subprocess():
    """Fresh interpreter pinned to a 4-device CPU mesh (the env var must
    predate jax's import, hence the subprocess): bitwise f32 equivalence,
    int8 tolerance, tensor-mode tolerance, and one shard per device."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    worker = os.path.join(os.path.dirname(__file__), "sharded_worker.py")
    proc = subprocess.run([sys.executable, worker, "--devices", "4"],
                          capture_output=True, text=True, env=env,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc.get("error") is None, doc
    assert doc["devices_visible"] == 4
    assert doc["f32_do_predict_bitwise"] and doc["f32_dispatch_bitwise"]
    assert doc["int8_within_tolerance"], doc["int8_max_err"]
    assert doc["tensor_within_tolerance"], doc["tensor_max_err"]
    # structural fan-out: the dispatched batch spans all 4 devices evenly
    assert doc["output_span_devices"] == 4
    assert all(n == 1 for n in doc["per_device_shards"].values())
    assert doc["tensor_sharded_param_leaves"] > 0
    assert doc["mesh_info"]["sharded_calls"] > 0


# -- bench flags --------------------------------------------------------------

def test_serving_bench_smoke_mesh(tmp_path, ctx):
    """serving_bench --smoke --mesh 4: the sharded A/B fields land in the
    --json document and no record is lost through the mesh path."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import serving_bench
    out_path = str(tmp_path / "bench.json")
    out = serving_bench.main(["--smoke", "--mesh", "4", "--sharding",
                              "batch", "--json", out_path])
    assert out["records"] > 0 and out["errors"] == 0
    assert out["mesh_devices"] == 4
    assert out["sharding"] == "batch"
    assert out["sharded_calls"] > 0
    assert out["sharded_samples_per_sec"] is not None
    doc = json.load(open(out_path))
    assert doc["results"][0]["mesh_devices"] == 4
