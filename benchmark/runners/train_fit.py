"""Runner ``train_fit``: data-parallel ``Estimator.fit`` from host numpy arrays.

The system under test is what a user calls: ``init_context()`` (default mesh, data
axis over every chip), ``Estimator(model, optimizer, loss).fit(x, y, batch_size,
steps_per_call)`` with float32 NHWC images and labels in host memory, through the
program's own batch assembly, device prefetcher and donating step.

The program's ``fit_step_seconds`` times the dispatch, and its listeners are handed
the loss as a device array; so the runner registers a listener, keeps the arrays,
and blocks on the loss of the call ``lag`` behind the newest (dispatch runs ahead as
in a user's fit, by a bounded amount).  The moment that block returns is the step's
completion stamp.  An ``end_trigger`` stops ``fit`` once the window is over.

Warm calls before the window (``warm_calls`` in the traffic file) cover at least one
whole epoch, because the program's epoch end stacks the epoch's losses in an eager
operation that compiles once per epoch length.
"""

from __future__ import annotations

import collections
import contextlib
import shutil
import tempfile
import time

import numpy as np

import reference
import xplane


def run(job: dict) -> dict:
    import jax

    from analytics_zoo_tpu.common import dtypes
    from analytics_zoo_tpu.common.context import init_context
    from analytics_zoo_tpu.common.triggers import ZooTrigger
    from analytics_zoo_tpu.estimator.estimator import Estimator
    from analytics_zoo_tpu.inference import aot
    from analytics_zoo_tpu.models.imageclassification import resnet
    from analytics_zoo_tpu.nn.optimizers import SGD

    cfg, traffic, chips = job["config"], job["traffic"], job["chips"]
    m, seed, trace = cfg["model"], int(job["seed"]), bool(job["trace"])
    ctx = init_context(seed=seed % (2 ** 31 - 1))      # default mesh: data = -1
    getattr(dtypes, cfg["dtype_policy"])()
    if ctx.data_parallel_size != chips or jax.device_count() != chips:
        raise RuntimeError(f"the default mesh spans {ctx.data_parallel_size} of "
                           f"{jax.device_count()} devices, the cell asks {chips}")
    batch = int(cfg["batch_per_chip"]) * chips
    side, classes = m["image_size"], m["num_classes"]
    model = resnet(m["depth"], num_classes=classes,
                   input_shape=(side, side, 3), stem=m["stem"])
    est = Estimator(model, loss=cfg["loss"], ctx=ctx, optimizer=SGD(
        lr=cfg["optimizer"]["lr"], momentum=cfg["optimizer"]["momentum"]))

    # data from the seed: two distinct global batches, tiled to the data set
    g = np.random.default_rng(seed)
    x2 = g.standard_normal((2 * batch, side, side, 3), dtype=np.float32)
    y2 = g.integers(0, classes, (2 * batch, 1)).astype(np.float32)
    reps = int(traffic["dataset_batches"]) // 2
    x, y = np.tile(x2, (reps, 1, 1, 1)), np.tile(y2, (reps, 1))

    span = jax.profiler.TraceAnnotation if trace \
        else (lambda name: contextlib.nullcontext())
    lag, k = int(traffic["lag"]), int(traffic["steps_per_call"])
    pending: collections.deque = collections.deque()
    stamps = []                       # (t, global_step, loss) of completed calls

    def complete_one():
        step, loss = pending.popleft()
        with span("bench.fit_wait_step"):
            value = float(jax.block_until_ready(loss))
        stamps.append((time.monotonic(), step, value))

    state = {"t0": None, "t1": None, "trace_dir": None, "tracing": None,
             "window_cm": None, "compiles0": None, "compiles1": None}

    def listener(step, loss):
        pending.append((step, loss))
        while len(pending) > lag:
            complete_one()
        now = time.monotonic()
        if state["t0"] is None:
            if len(stamps) >= int(traffic["warm_calls"]):
                state["t0"] = stamps[-1][0]
                state["t1"] = state["t0"] + float(job["seconds"])
                state["compiles0"] = aot.COMPILE_STATS.snapshot()
            return
        if not trace:
            return
        if state["tracing"] is None and now >= state["t0"] + min(
                xplane.TRACE_AFTER_S, job["seconds"] / 4):
            state["trace_dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(state["trace_dir"])
            state["window_cm"] = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            state["window_cm"].__enter__()
            state["tracing"] = time.monotonic() + xplane.TRACE_SECONDS
        elif state["window_cm"] is not None and now >= state["tracing"]:
            state["window_cm"].__exit__(None, None, None)
            state["window_cm"] = None
            jax.profiler.stop_trace()

    class WindowOver(ZooTrigger):
        def __call__(self, tstate) -> bool:
            over = state["t1"] is not None and time.monotonic() >= state["t1"]
            if over and state["compiles1"] is None:
                state["compiles1"] = aot.COMPILE_STATS.snapshot()
            return over

    # set-up: initial parameters kept for the check, then ONE step of the
    # program on a known batch (the first global batch), whose loss the plain
    # reference must reproduce; the same compiled step then runs the window
    est._listeners.append(listener)
    est._ensure_init(x[:batch])
    params0 = jax.device_get(est.params)      # to the host: the step donates
    est.fit(x[:k * batch], y[:k * batch], batch_size=batch, epochs=1,
            shuffle=False, verbose=False, steps_per_call=k)
    while pending:
        complete_one()
    first_loss, check_steps = stamps[-1][2], est.global_step
    stamps.clear()

    est.fit(x, y, batch_size=batch, epochs=10 ** 6, verbose=False,
            steps_per_call=k, end_trigger=WindowOver())
    while pending:
        complete_one()
    if state["window_cm"] is not None:     # the window ended inside the trace
        state["window_cm"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = state["compiles1"]["compile_requests"] \
        - state["compiles0"]["compile_requests"]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:chips])
    memory = jax.local_devices()[0].memory_stats()
    trace_doc = None
    if state["trace_dir"] is not None:
        try:
            trace_doc = xplane.reduce_dir(state["trace_dir"], chips)
        finally:
            shutil.rmtree(state["trace_dir"], ignore_errors=True)

    t0, t1 = state["t0"], state["t1"]
    inside = [s for s in stamps if t0 <= s[0] < t1]
    steps = len(inside) * k
    losses_ok = all(np.isfinite(s[2]) for s in stamps)
    counted_ok = est.global_step == check_steps + len(stamps) * k
    rows = est._shard(x[:batch])[0].addressable_shards[0].data.shape[0]
    check = reference.check_first_loss(
        params0, x[:batch], y[:batch], m["depth"],
        model.name, first_loss)
    check.update(losses_finite=losses_ok, steps_counted=counted_ok,
                 rows_per_device=rows)
    check["ok"] = bool(check["ok"] and losses_ok and counted_ok
                       and rows == cfg["batch_per_chip"])
    return {"correct": check["ok"] and compiles == 0 and steps > 0,
            "attempted": steps, "failed": 0 if losses_ok else steps,
            "window": [t0, t1], "work": [(s[0], k * batch) for s in stamps],
            "steps_per_stamp": k, "rate_span": "stamps",
            "setup_seconds": t0 - job["t_process"],
            "memory_peak_bytes": peak, "trace": trace_doc, "chips": chips,
            "peaks": job["peaks"], "config": cfg, "traffic": traffic,
            "notes": {"check": check, "compiles_in_window": compiles,
                      "memory": memory,
                      "global_step": est.global_step,
                      "last_loss": stamps[-1][2] if stamps else None}}
