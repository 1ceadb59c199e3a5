#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the three main paths once, through the entry points a
user calls, at the full width of models the repo supports, then checks the
Pallas kernels one by one:

- train:    ``init_context()`` + ``Estimator(resnet(50, stem="s2d")).fit`` at
            224x224x3, batch 128 per chip, ``dtypes.mixed_bf16()`` — single
            steps and one ``steps_per_call > 1`` call, through the device
            prefetcher and the donating step.
- predict:  the same ResNet-50 served by ``manager.serve_from_config``
            (config -> load_model -> build_queue -> ServingParams), warm-up
            and HTTP gateway on; records in over the binary wire and over
            ``POST /v1/enqueue``, results out over ``OutputQueue.query_many``
            and ``GET /v1/result/<uri>``.
- generate: ``TransformerLM`` at GPT-2-small width (vocab 50257, hidden 768,
            12 heads, 12 layers, max_len 1024) under ``ClusterServing`` with
            ``generation={"paged": True}``: queue/gateway -> ContinuousBatcher
            -> kvpool -> decode_paged -> the Pallas paged-attention kernel.
- kernels:  paged decode (float, int8), flash forward + both backward
            kernels through ``dot_product_attention`` under jit+grad,
            ``w8a8_matmul`` and ``w4a16_matmul`` at real layer shapes — each
            must be a Mosaic ``tpu_custom_call`` in the lowered program and
            match its in-file XLA reference.

Depth is never cut here; the LADDERS are short (max_batch 8, scales off, two
prefill buckets, 4 decode slots) — width finds the tiling and VMEM problems,
ladder length only buys compile time.  Weights are random, from a seed.
The generate leg sends one late prompt that repeats the full KV blocks of an
earlier one, so the prefix-sharing prefill runs at full width too.

Contract: exits non-zero, with one line on stderr and no result line, when
jax's first device is not a TPU or when the package is not importable.  Any
failed check in any leg raises out of ``main`` (there is no ``except`` between
a leg and the exit code).  On success the LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
It starts no process: the chip belongs to this one.

With more than one chip the train leg uses the default ``data = -1`` mesh
(global batch 128 x chips) and the predict leg serves ``sharding="batch"``
over all of them; both assert that every device really holds shards.

Tolerances (stated here, asserted below; measured values are printed):

- generate: every served token's logit, in a float32 highest-precision
  teacher-forced forward of the same weights, is within ``GEN_LOGIT_TOL`` of
  that position's maximum.  f32 weights ride bf16 MXU passes on this chip, so
  near-ties may resolve differently from ``TransformerLM.generate``; the
  first-token and whole-sequence match counts against it are printed, the
  tolerance (first step included) is what is asserted.
- predict: served probabilities vs a direct ``model.apply`` of the same
  weights under the same dtype policy: ``PREDICT_RTOL`` of the largest
  probability (another batch size is another bf16 program; the error
  varies run to run with how the engine batched the records).
- kernels: relative error ``max|a - b| / max|b|`` against the XLA reference
  at highest matmul precision — ``KERNEL_TOL`` per kernel.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import socket
import sys
import tempfile
import time
import urllib.request

# each about 4-10x what one v5e chip measured (PR 21; values are printed)
GEN_LOGIT_TOL = 0.05
PREDICT_RTOL = 0.05         # 0.008-0.011 measured: varies with how the
#                             engine happened to batch the records
KERNEL_TOL = {"paged_attention": 1e-4, "paged_attention_int8": 1e-4,
              "flash_fwd": 1e-2, "flash_bwd": 2e-2,
              "w8a8_matmul": 1e-6, "w4a16_matmul": 1e-2}
MOSAIC = "tpu_custom_call"

# the sizes the driver's run uses; tests pass toy ones
FULL = {
    "train": {"depth": 50, "image": 224, "classes": 1000,
              "batch_per_chip": 128, "single_steps": 2, "scanned_calls": 1,
              "steps_per_call": 2},
    "predict": {"depth": 50, "image": 224, "classes": 1000, "max_batch": 8,
                "records": 64, "http_records": 4},
    "generate": {"vocab": 50257, "hidden": 768, "heads": 12, "layers": 12,
                 "max_len": 1024, "slots": 4, "max_tokens": 48,
                 "prompt_lens": [5, 12, 23, 40, 57, 9],
                 "prefill_buckets": [16, 64], "block_len": 16,
                 "http_requests": 2},
    # paged: GPT-2-small width, then the benchmark cells' shape (gpt2-large,
    # 8 slots, lane 1024: eight table groups a row, ragged over all of them)
    "kernels": {"paged": [{"rows": 4, "heads": 12, "head_dim": 64,
                           "block_len": 16, "n_table": 8,
                           "lengths": [128, 65, 17, 1]},
                          {"rows": 8, "heads": 20, "head_dim": 64,
                           "block_len": 16, "n_table": 64,
                           "lengths": [1, 16, 17, 128, 129, 600, 1023,
                                       1024]}],
                "flash": {"batch": 1, "heads": 12, "seq": 2048,
                          "head_dim": 64},
                "matmul": [(512, 2048, 1000), (512, 768, 3072)]},
}


class SmokeFailure(Exception):
    """A check failed: the run must not reach exit 0."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def report(leg: str, **doc) -> None:
    print(json.dumps({"leg": leg, **doc}), flush=True)


def _rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    check(a.shape == b.shape, f"shape {a.shape} != reference {b.shape}")
    check(bool(np.isfinite(a).all()), "non-finite values in kernel output")
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: bytes = None, ctype: str = None):
    req = urllib.request.Request(url, data=body, method=method)
    if ctype:
        req.add_header("Content-Type", ctype)
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, json.loads(resp.read().decode("utf-8"))


def run_leg(name: str, leg, *args) -> None:
    """Run one leg and report it with its wall time and its COMPILE_STATS
    delta (the persistent-cache evidence: a cache-warm run shows zero
    ``cache_misses`` in every leg), then drop its executables' HBM."""
    import jax

    from analytics_zoo_tpu.inference import aot
    before, t0 = aot.COMPILE_STATS.snapshot(), time.monotonic()
    doc = leg(*args)
    after = aot.COMPILE_STATS.snapshot()
    report(name, **doc, seconds=round(time.monotonic() - t0, 1),
           compile={k: round(after[k] - before[k], 2) for k in after})
    gc.collect()
    jax.clear_caches()


def _assert_spans_all_devices(tree, what: str) -> list:
    """Every leaf of ``tree`` is placed on every device, and every device
    holds live bytes — code that only ever met virtual CPU devices may
    have put everything on device 0.  Returns bytes in use per device."""
    import jax
    n = jax.device_count()
    for leaf in jax.tree.leaves(tree):
        got = len(leaf.sharding.device_set)
        check(got == n, f"{what}: a leaf of shape {leaf.shape} sits on "
                        f"{got} of {n} devices")
    in_use = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats is not None:       # CPU devices report none
            check(stats.get("bytes_in_use", 0) > 0,
                  f"{what}: device {d.id} holds no bytes")
            in_use.append(stats["bytes_in_use"])
    return in_use


def _wait_warm(serving, timeout_s: float) -> dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        state = serving.warmup_state()
        if state.get("state") not in ("pending", "warming"):
            return state
        time.sleep(0.05)
    raise SmokeFailure(f"warm-up did not finish in {timeout_s:.0f}s: "
                       f"{serving.warmup_state()}")


def _assert_healthy(serving, leg: str) -> dict:
    """The engine serves on through failures by design (quarantine,
    shedding, degraded warm-up); here any of them is a failed run."""
    from analytics_zoo_tpu.serving.client import OutputQueue
    h = serving.health()
    warm = h["warmup"]
    check(warm.get("state") == "ready" and not warm.get("failed"),
          f"{leg}: warm-up is {warm.get('state')!r} with "
          f"{warm.get('failed')} failed program(s): {warm}")
    check(h["dead_lettered"] == 0, f"{leg}: {h['dead_lettered']} record(s) "
                                   "quarantined")
    dead = OutputQueue(serving.queue).dead_letters()
    check(not dead, f"{leg}: dead-letter channel holds {len(dead)} "
                    f"record(s): {dead[:2]}")
    check(h["shed"] == 0, f"{leg}: {h['shed']} record(s) shed")
    check(h["running"], f"{leg}: workers not running: {h['workers']}")
    for name, w in h["workers"].items():
        check(w.get("restarts", 0) == 0,
              f"{leg}: worker {name} restarted: {w}")
    return h


# -- leg: kernels --------------------------------------------------------------

def leg_kernels(cfg: dict, impl: str = "pallas") -> dict:
    """Each Pallas kernel against its XLA reference.  ``impl="pallas"``
    (the chip) demands a Mosaic custom call in the lowered program;
    ``impl="interpret"`` (CPU tests, chosen explicitly) demands none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.inference.quantize import kv_pack_int8
    from analytics_zoo_tpu.ops import quant_matmul as qm
    from analytics_zoo_tpu.ops.attention import (_attention_xla,
                                                 dot_product_attention)
    from analytics_zoo_tpu.ops.paged_attention import (paged_attention,
                                                       paged_attention_xla)

    compiled = impl == "pallas"
    errors = {}

    def run(name: str, fn, ref_fn, args, n_calls: int = 1, tol_key=None):
        """lower -> count Mosaic calls -> run -> compare."""
        jitted = jax.jit(fn)
        calls = jitted.lower(*args).as_text().count(MOSAIC)
        if compiled:
            check(calls >= n_calls,
                  f"{name}: {calls} Mosaic call(s) in the lowered program, "
                  f"expected {n_calls} — the kernel did not run as Pallas")
        else:
            check(calls == 0, f"{name}: interpret mode lowered {calls} "
                              "Mosaic call(s)")
        out = jax.block_until_ready(jitted(*args))
        with jax.default_matmul_precision("highest"):
            ref = jax.block_until_ready(jax.jit(ref_fn)(*args))
        outs, refs = jax.tree.leaves(out), jax.tree.leaves(ref)
        err = max(_rel_err(o, r) for o, r in zip(outs, refs))
        tol = KERNEL_TOL[tol_key or name]
        check(err <= tol, f"{name}: relative error {err:.3e} above {tol:g} "
                          "against the XLA reference")
        errors[name] = {"rel_err": float(f"{err:.3e}"), "tol": tol,
                        "mosaic_calls": calls}

    g = np.random.default_rng(0)

    # paged decode, float and int8: ragged lengths, permuted block order
    for pc in cfg["paged"]:
        A, nh, hd = pc["rows"], pc["heads"], pc["head_dim"]
        bl, nt = pc["block_len"], pc["n_table"]
        n_blocks = 1 + A * nt
        shape = f"{A}x{nh}x{hd}x{nt}"
        q = g.normal(size=(A, nh, hd)).astype(np.float32)
        k4 = g.normal(size=(n_blocks, bl, nh, hd)).astype(np.float32)
        v4 = g.normal(size=(n_blocks, bl, nh, hd)).astype(np.float32)
        tables = g.permutation(np.arange(1, n_blocks)) \
            .reshape(A, nt).astype(np.int32)
        lens = np.asarray(pc["lengths"], np.int32)

        def fold(x):
            return np.ascontiguousarray(x).reshape(n_blocks, bl, nh * hd)

        run(f"paged_attention_{shape}",
            lambda *a: paged_attention(*a, impl=impl),
            paged_attention_xla, (q, fold(k4), fold(v4), tables, lens),
            tol_key="paged_attention")
        qk, ks = kv_pack_int8(k4)
        qv, vs = kv_pack_int8(v4)
        run(f"paged_attention_int8_{shape}",
            lambda q_, k_, v_, t_, l_, ks_, vs_: paged_attention(
                q_, k_, v_, t_, l_, ks_, vs_, impl=impl),
            paged_attention_xla,
            (q, fold(np.asarray(qk)), fold(np.asarray(qv)), tables, lens,
             np.asarray(ks), np.asarray(vs)), tol_key="paged_attention_int8")

    # flash forward + dq + dkv through the dispatching entry point.  On the
    # chip use_flash stays None: auto-selection is part of what is checked.
    fc = cfg["flash"]
    shape = (fc["batch"], fc["heads"], fc["seq"], fc["head_dim"])
    fq, fk, fv = (jnp.asarray(g.normal(size=shape), jnp.bfloat16)
                  for _ in range(3))
    use_flash = None if compiled else True

    def flash_loss(q_, k_, v_):
        o = dot_product_attention(q_, k_, v_, causal=True,
                                  use_flash=use_flash)
        return (o.astype(jnp.float32) ** 2).sum()

    def xla_loss(q_, k_, v_):
        o = _attention_xla(q_.astype(jnp.float32), k_.astype(jnp.float32),
                           v_.astype(jnp.float32), causal=True)
        return (o ** 2).sum()

    run("flash_fwd",
        lambda *a: dot_product_attention(*a, causal=True,
                                         use_flash=use_flash),
        lambda q_, k_, v_: _attention_xla(
            q_.astype(jnp.float32), k_.astype(jnp.float32),
            v_.astype(jnp.float32), causal=True), (fq, fk, fv))
    run("flash_bwd", jax.grad(flash_loss, argnums=(0, 1, 2)),
        jax.grad(xla_loss, argnums=(0, 1, 2)), (fq, fk, fv), n_calls=3)

    # quantized matmuls at real layer shapes
    for m, k, n in cfg["matmul"]:
        xq = g.integers(-127, 128, (m, k)).astype(np.int8)
        wq = g.integers(-127, 128, (k, n)).astype(np.int8)
        sc = (g.random(n).astype(np.float32) + 0.5) * 1e-3
        run(f"w8a8_matmul_{m}x{k}x{n}",
            lambda *a: qm.w8a8_matmul(*a, impl=impl),
            qm.w8a8_matmul_xla, (xq, wq, sc), tol_key="w8a8_matmul")
        groups = k // 64
        x = g.normal(size=(m, k)).astype(np.float32)
        w4 = qm.pack_int4(g.integers(-7, 8, (k, n)).astype(np.int8))
        sg = (g.random((groups, n)).astype(np.float32) + 0.5) * 1e-2
        check(qm._w4_pallas_ok(k, groups),
              f"w4a16 shape K={k} groups={groups} is outside the kernel's "
              "contract: the smoke must exercise the kernel")
        run(f"w4a16_matmul_{m}x{k}x{n}",
            lambda *a: qm.w4a16_matmul(*a, impl=impl),
            qm.w4a16_matmul_xla, (x, w4, sg), tol_key="w4a16_matmul")
    return {"impl": impl, "kernels": errors}


# -- leg: train ----------------------------------------------------------------

def leg_train(cfg: dict) -> dict:
    import jax
    import numpy as np

    from analytics_zoo_tpu.common import dtypes
    from analytics_zoo_tpu.common.context import init_context
    from analytics_zoo_tpu.estimator.estimator import Estimator
    from analytics_zoo_tpu.models.imageclassification import resnet
    from analytics_zoo_tpu.nn.optimizers import SGD

    ctx = init_context(seed=0)              # default mesh: data = -1
    dtypes.mixed_bf16()
    n_dev = jax.device_count()
    check(ctx.data_parallel_size == n_dev,
          f"default mesh spans {ctx.data_parallel_size} of {n_dev} devices")
    batch = cfg["batch_per_chip"] * n_dev
    k = cfg["steps_per_call"]
    img = cfg["image"]
    model = resnet(cfg["depth"], num_classes=cfg["classes"],
                   input_shape=(img, img, 3), stem="s2d")
    est = Estimator(model, optimizer=SGD(lr=0.01, momentum=0.9),
                    loss="sparse_categorical_crossentropy", ctx=ctx)
    losses = []
    est._listeners.append(lambda step, loss: losses.append((step, loss)))

    g = np.random.default_rng(0)

    def data(n_batches):
        x = g.standard_normal((n_batches * batch, img, img, 3),
                              dtype=np.float32)
        y = g.integers(0, cfg["classes"], (n_batches * batch, 1)) \
            .astype(np.float32)
        return x, y

    x, y = data(cfg["single_steps"])
    est.fit(x, y, batch_size=batch, epochs=1, shuffle=False, verbose=False)
    x, y = data(cfg["scanned_calls"] * k)
    est.fit(x, y, batch_size=batch, epochs=1, shuffle=False, verbose=False,
            steps_per_call=k)
    want = cfg["single_steps"] + cfg["scanned_calls"] * k
    check(est.global_step == want,
          f"took {est.global_step} optimizer steps, expected {want}")
    values = [float(l) for _, l in losses]
    check(len(values) == cfg["single_steps"] + cfg["scanned_calls"],
          f"{len(values)} step callbacks for {want} steps")
    check(all(np.isfinite(v) for v in values), f"non-finite loss: {values}")
    check(values[0] != values[-1], f"loss did not change: {values}")
    doc = {"steps": est.global_step, "global_batch": batch,
           "devices": n_dev, "losses": [round(v, 4) for v in values]}
    if n_dev > 1:
        _assert_spans_all_devices(est.params, "train params")
        sx = est._shard(x[:batch])[0]       # what the fit loop feeds
        doc["bytes_in_use_per_device"] = _assert_spans_all_devices(
            sx, "train batch")
        rows = sx.addressable_shards[0].data.shape[0]
        check(rows == batch // n_dev,
              f"a device holds {rows} rows of the {batch}-row batch")
        doc["batch_rows_per_device"] = rows
    return doc


# -- leg: predict serving ------------------------------------------------------

# the deployment's topology file: what `model.topology` names in config.yaml
_TOPOLOGY = """\
from analytics_zoo_tpu.models.imageclassification import resnet


def build_model():
    return resnet({depth}, num_classes={classes},
                  input_shape=({image}, {image}, 3), stem="s2d")
"""


def leg_predict(cfg: dict, workdir: str) -> dict:
    import jax
    import numpy as np

    from analytics_zoo_tpu.common import dtypes
    from analytics_zoo_tpu.models.imageclassification import resnet
    from analytics_zoo_tpu.serving import manager, wire
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue

    dtypes.mixed_bf16()
    n_dev = jax.device_count()
    img, classes = cfg["image"], cfg["classes"]
    topo = os.path.join(workdir, "topology.py")
    with open(topo, "w") as f:
        f.write(_TOPOLOGY.format(**cfg))
    model = resnet(cfg["depth"], num_classes=classes,
                   input_shape=(img, img, 3), stem="s2d")
    model.init_weights(jax.random.PRNGKey(1))
    g = np.random.default_rng(2)
    n, n_http = cfg["records"], cfg["http_records"]
    xs = g.standard_normal((n + n_http, img, img, 3), dtype=np.float32)
    sample = [0, 1, n - 1, n, n + n_http - 1]
    # A random init with identity BatchNorm statistics saturates the
    # softmax — one class at probability 1.0 whatever the input — which
    # would make every comparison below trivial.  Shrink the classifier
    # head until the output is informative (one program: the weights are
    # arguments, and the reference below reuses it).
    direct = jax.jit(
        lambda p, s, x: model.apply(p, s, x, training=False)[0])
    head = model._params[f"resnet{cfg['depth']}_fc"]
    informative = max(0.1, 2.0 / classes)
    for _ in range(60):
        if float(direct(model._params, model._state,
                        xs[sample]).max()) < informative:
            break
        head["W"], head["b"] = head["W"] * 0.5, head["b"] * 0.5
    else:
        raise SmokeFailure("could not de-saturate the classifier head")
    weights = os.path.join(workdir, "weights.npz")
    model.save_weights(weights)
    port = _free_port()
    params = {"batch_size": cfg["max_batch"], "max_batch": cfg["max_batch"],
              "top_n": classes, "http_port": port,
              "warmup": {"shape": [img, img, 3], "scales": "off",
                         "max_batch": cfg["max_batch"]}}
    if n_dev > 1:
        params.update(sharding="batch", mesh_shape=n_dev)
    config = os.path.join(workdir, "config.yaml")
    with open(config, "w") as f:     # JSON is YAML
        json.dump({"model": {"path": weights, "topology": topo},
                   "data": {"src": "file:" + os.path.join(workdir, "queue")},
                   "params": params}, f)

    serving = manager.serve_from_config(config)
    serving.start()
    try:
        warm = _wait_warm(serving, 900.0)
        check(warm.get("state") == "ready" and not warm.get("failed"),
              f"predict warm-up: {warm}")
        cin = InputQueue(serving.queue)
        uris = [f"rec-{i}" for i in range(n)]
        for uri, x in zip(uris, xs):
            cin.enqueue_tensor(uri, x, wire="bin")
        base = f"http://127.0.0.1:{port}"
        http_uris = [f"http-{i}" for i in range(n_http)]
        for uri, x in zip(http_uris, xs[n:]):
            status, ack = _http(
                "POST", base + "/v1/enqueue?timeout_s=300",
                wire.encode_tensor_frame(uri, np.ascontiguousarray(x)),
                "application/octet-stream")
            check(status == 200 and ack.get("uri") == uri,
                  f"POST /v1/enqueue answered {status} {ack}")
        results = OutputQueue(serving.queue).query_many(uris,
                                                        timeout_s=300.0)
        for uri in http_uris:
            status, body = _http(
                "GET", f"{base}/v1/result/{uri}?timeout_s=60")
            check(status == 200, f"GET /v1/result/{uri} -> {status} {body}")
            results[uri] = body

        # every result a value: the full ranked (class, prob) list
        probs = np.zeros((n + n_http, classes), np.float64)
        for i, uri in enumerate(uris + http_uris):
            r = results[uri]
            check(isinstance(r, dict) and "value" in r,
                  f"{uri}: no value in result {str(r)[:200]}")
            pairs = r["value"]
            check(len(pairs) == classes, f"{uri}: {len(pairs)} classes")
            for c, p in pairs:
                probs[i, int(c)] = p
        check(bool(np.isfinite(probs).all()), "non-finite probabilities")
        check(bool(np.allclose(probs.sum(axis=1), 1.0, atol=2e-2)),
              "served rows are not probability vectors: sums "
              f"{probs.sum(axis=1)[:4]}")
        # against a direct apply of the same weights, same dtype policy
        ref = np.asarray(direct(model._params, model._state, xs[sample]),
                         np.float64)
        err = float(np.abs(probs[sample] - ref).max() / ref.max())
        check(err <= PREDICT_RTOL,
              f"served probabilities differ from a direct model.apply by "
              f"{err:.3e} of the peak (> {PREDICT_RTOL})")
        h = _assert_healthy(serving, "predict")
        check(h["total_records"] == n + n_http,
              f"engine counted {h['total_records']} records")
        in_use = None
        if n_dev > 1:
            in_use = _assert_spans_all_devices(serving.model._params,
                                               "predict params")
            check(serving.model.mesh_devices == n_dev,
                  f"predict mesh has {serving.model.mesh_devices} devices")
    finally:
        serving.shutdown(drain_s=5.0)
    return {"records_in": n + n_http, "values_out": len(results),
            "http_records": n_http, "rel_err_vs_apply": float(f"{err:.3e}"),
            "quarantined": h["dead_lettered"], "shed": h["shed"],
            "warmup": {k: warm.get(k) for k in
                       ("state", "total", "failed", "seconds")},
            "devices": n_dev, "bytes_in_use_per_device": in_use}


# -- leg: generate serving -----------------------------------------------------

def leg_generate(cfg: dict, expect_mosaic: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    from analytics_zoo_tpu.serving import wire
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue

    class RecordingQueue(InProcQueue):
        """The in-process backend, keeping every streamed partial so the
        prefix property is checked on all of them, not on a lucky poll."""

        def __init__(self):
            super().__init__()
            self.partials = []

        def put_partial(self, key, value):
            self.partials.append((key, list(value["tokens"])))
            return super().put_partial(key, value)

    lm = TransformerLM(vocab_size=cfg["vocab"], hidden=cfg["hidden"],
                       n_head=cfg["heads"], n_layers=cfg["layers"],
                       max_len=cfg["max_len"])
    lm_params = lm.build(jax.random.PRNGKey(3))
    im = InferenceModel().do_load_model(lm, lm_params, {})
    port = _free_port()
    budget = cfg["max_tokens"]
    queue = RecordingQueue()
    serving = ClusterServing(im, queue, ServingParams(
        max_batch=8, max_wait_ms=2.0, http_port=port, warmup=True,
        generation={"paged": True, "max_active_slots": cfg["slots"],
                    "max_tokens": budget,
                    "max_prompt_len": max(cfg["prefill_buckets"]),
                    "prefill_buckets": cfg["prefill_buckets"],
                    "block_len": cfg["block_len"], "stream_interval": 8,
                    "decode_quantum": 4}))
    serving.start()
    try:
        warm = _wait_warm(serving, 900.0)
        check(warm.get("state") == "ready" and not warm.get("failed"),
              f"generate warm-up: {warm}")
        # the program the hot path runs, not a flag: the compiled paged
        # decode step must contain the Mosaic kernel
        decode = [exe for key, exe in serving._batcher._programs.items()
                  if key[0] == "pdecode"]
        check(len(decode) == 1, f"{len(decode)} paged decode programs")
        calls = decode[0].as_text().count(MOSAIC)
        if expect_mosaic:
            check(calls >= 1, "the compiled paged decode program holds no "
                              "Mosaic custom call: decode is not running "
                              "the Pallas kernel")
        else:
            check(calls == 0, f"{calls} Mosaic call(s) off the chip")

        g = np.random.default_rng(4)
        prompts = {f"gen-{i}": g.integers(1, cfg["vocab"], n).astype(np.int32)
                   for i, n in enumerate(cfg["prompt_lens"])}
        rids = list(prompts)
        http_rids = rids[-cfg["http_requests"]:] if cfg["http_requests"] \
            else []
        cin = InputQueue(queue)
        base = f"http://127.0.0.1:{port}"
        for rid in rids:
            tokens = np.ascontiguousarray(prompts[rid].astype("<f4"))
            if rid in http_rids:
                status, ack = _http(
                    "POST", base + "/v1/enqueue?timeout_s=300",
                    wire.encode_tensor_frame(rid, tokens),
                    "application/octet-stream")
                check(status == 200 and ack.get("uri") == rid,
                      f"POST /v1/enqueue answered {status} {ack}")
            else:
                cin.enqueue_tensor(rid, tokens, wire="bin")
        results = OutputQueue(queue).query_many(
            [r for r in rids if r not in http_rids], timeout_s=300.0)
        for rid in http_rids:
            status, body = _http("GET",
                                 f"{base}/v1/result/{rid}?timeout_s=60")
            check(status == 200 and "value" in body,
                  f"GET /v1/result/{rid} -> {status} {str(body)[:200]}")
            results[rid] = body
        # a late arrival repeating every FULL KV block of a finished
        # request's prompt (what the prefix index keys on): admitted
        # through the prefix cache, suffix-only prefill.  (Every result is
        # in by now, the gateway's too: a donor admitted at the same
        # boundary as its sharer has registered nothing yet.)
        donor = max(prompts, key=lambda r: len(prompts[r]))
        shared = len(prompts[donor]) // cfg["block_len"] * cfg["block_len"]
        check(shared > 0, "the longest prompt fills no KV block")
        prompts["gen-shared"] = np.concatenate(
            [prompts[donor][:shared],
             g.integers(1, cfg["vocab"], 7).astype(np.int32)])
        rids.append("gen-shared")
        cin.enqueue_tensor("gen-shared", np.ascontiguousarray(
            prompts["gen-shared"].astype("<f4")), wire="bin")
        results.update(OutputQueue(queue).query_many(["gen-shared"],
                                                     timeout_s=300.0))

        served = {}
        for rid in rids:
            r = results[rid]
            check(isinstance(r, dict) and "value" in r,
                  f"{rid}: no value in result {str(r)[:200]}")
            toks = [int(t) for t in r["value"]["tokens"]]
            check(len(toks) == budget and r["value"]["finish_reason"]
                  == "length", f"{rid}: {len(toks)} tokens, "
                               f"{r['value']['finish_reason']}")
            check(all(0 <= t < cfg["vocab"] for t in toks),
                  f"{rid}: token outside the vocabulary")
            served[rid] = toks
        streamed = {rid: 0 for rid in rids}
        for rid, toks in queue.partials:
            check(toks == served[rid][:len(toks)],
                  f"{rid}: a streamed partial is not a prefix of the "
                  "terminal result")
            streamed[rid] += 1
        check(all(streamed.values()),
              f"requests with no streamed partial: {streamed}")

        # the reference: TransformerLM.generate on the same weights (one
        # right-padded batch), and a float32 highest-precision teacher-
        # forced forward over prompt + served tokens
        pmax = max(len(p) for p in prompts.values())
        padded = np.zeros((len(rids), pmax), np.int32)
        lens = np.asarray([len(prompts[r]) for r in rids], np.int32)
        for i, rid in enumerate(rids):
            padded[i, :lens[i]] = prompts[rid]
        ref_tokens = np.asarray(lm.generate(lm_params, padded,
                                            max_tokens=budget, lengths=lens))
        full = np.zeros((len(rids), pmax + budget), np.int32)
        for i, rid in enumerate(rids):
            full[i, :lens[i]] = prompts[rid]
            full[i, lens[i]:lens[i] + budget] = served[rid]
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(jax.jit(lm.call)(lm_params,
                                                 jnp.asarray(full)))
        worst, first, exact = 0.0, 0, 0
        for i, rid in enumerate(rids):
            rows = logits[i, lens[i] - 1:lens[i] - 1 + budget]
            margin = rows.max(axis=-1) - rows[np.arange(budget),
                                              served[rid]]
            worst = max(worst, float(margin.max()))
            first += int(served[rid][0] == int(ref_tokens[i, 0]))
            exact += int(served[rid] == [int(t) for t in ref_tokens[i]])
        check(worst <= GEN_LOGIT_TOL,
              f"a served token (first step included) trails the "
              f"reference's best logit by {worst:.4f} (> {GEN_LOGIT_TOL})")
        h = _assert_healthy(serving, "generate")
        gen = h["generation"]
        check(gen["finished"] == len(rids),
              f"scheduler finished {gen['finished']} of {len(rids)}")
        pool = gen.get("pool") or {}
        check(pool.get("exhausted", 0) == 0, f"KV pool ran dry: {pool}")
        check(pool.get("prefix_hits", 0) >= 1,
              f"the shared-prefix prompt missed the prefix cache: {pool}")
    finally:
        serving.shutdown(drain_s=5.0)
    return {"requests": len(rids), "http_requests": len(http_rids),
            "tokens_per_request": budget,
            "partials_streamed": sum(streamed.values()),
            "max_logit_margin": round(worst, 5), "logit_tol": GEN_LOGIT_TOL,
            "first_token_match_vs_generate": f"{first}/{len(rids)}",
            "exact_match_vs_generate": f"{exact}/{len(rids)}",
            "prefix_hits": pool["prefix_hits"],
            "decode_mosaic_calls": calls,
            "warmup": {k: warm.get(k) for k in
                       ("state", "total", "failed", "seconds")}}


# -- entry ---------------------------------------------------------------------

def _device_or_refuse() -> dict:
    """The device as jax reports it; anything but a TPU is a refusal —
    one line on stderr, a non-zero exit, nothing on stdout."""
    try:
        import jax
        dev = jax.devices()[0]
    except Exception as e:  # noqa: BLE001 — no backend at all is a refusal
        print(f"chip_smoke: refused — jax found no device "
              f"({type(e).__name__}: {str(e)[:200]})", file=sys.stderr)
        raise SystemExit(2)
    if dev.platform != "tpu":
        print(f"chip_smoke: refused — jax's first device is "
              f"{dev.platform!r} ({dev.device_kind}), not a TPU; this script "
              "only runs on the chip", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _versions() -> dict:
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main() -> int:
    device = _device_or_refuse()
    try:
        import analytics_zoo_tpu  # noqa: F401
        from analytics_zoo_tpu.inference import aot
    except ImportError as e:
        print(f"chip_smoke: refused — the analytics_zoo_tpu package is not "
              f"importable from {os.getcwd()} ({e})", file=sys.stderr)
        return 2
    t0 = time.monotonic()
    report("device", **device, versions=_versions(),
           compile_cache_dir=aot.enable_persistent_cache())
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        run_leg("train", leg_train, FULL["train"])
        run_leg("predict", leg_predict, FULL["predict"], workdir)
        run_leg("generate", leg_generate, FULL["generate"])
        run_leg("kernels", leg_kernels, FULL["kernels"])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    report("total", seconds=round(time.monotonic() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
