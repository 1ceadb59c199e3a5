"""Runner ``serve_generate``: a language model served for generation.

The system under test is started exactly as a deployment (and ``chip_smoke.py``)
starts it: ``ClusterServing(InferenceModel, InProcQueue, ServingParams(warmup=True,
generation={...}))``.  Load comes from ``loadgen.LoadGenerator`` in this process (the
chip belongs to one process); a request is one ``queue.xadd`` of a record carrying
its own answer length as ``"gen": {"max_tokens": n}``.  Times are taken at the
engine's output boundary, in ``StampQueue.put_partial`` / ``put_result(s)``.

``Session`` is split into ``start`` / ``measure`` / ``close`` so that
``benchmark/sweep.py`` can measure several rates behind one set-up.
"""

from __future__ import annotations

import base64
import contextlib
import shutil
import tempfile
import time

import numpy as np

import loadgen
import reference
import xplane
from analytics_zoo_tpu.serving.queues import InProcQueue

TTFT_HISTOGRAM = "serving_time_to_first_token_seconds"
SAMPLE_S = 0.25                      # counters are polled at 4 Hz, never faster


class StampQueue(InProcQueue):
    """The in-process backend with a clock on its output side: every streamed
    partial and every terminal result is stamped as the engine hands it over."""

    def __init__(self, span=None):
        super().__init__()
        self.stamps = {}             # rid -> [(t, tokens so far)]
        self.finals = {}             # rid -> (t, result)
        self.on_final = None
        self._span = span or (lambda name: contextlib.nullcontext())

    def put_partial(self, key, value):
        with self._span("bench.engine_flush"):
            self.stamps.setdefault(key, []).append(
                (time.monotonic(), int(value.get("n", 0))))
            return super().put_partial(key, value)

    def _final(self, key, value):
        self.finals[key] = (time.monotonic(), value)
        if self.on_final is not None:
            self.on_final()

    def put_result(self, key, value):
        with self._span("bench.engine_flush"):
            self._final(key, value)
            return super().put_result(key, value)

    def put_results(self, pairs):
        with self._span("bench.engine_flush"):
            pairs = list(pairs)
            for key, value in pairs:
                self._final(key, value)
            return super().put_results(pairs)

    def put_error(self, key, error, record=None, trace_id=None):
        self._final(key, {"error": str(error)})
        return super().put_error(key, error, record, trace_id)


class Session:
    def __init__(self, job: dict):
        self.job = job
        self.cfg, self.traffic = job["config"], job["traffic"]
        self.trace = bool(job.get("trace"))
        self.serving = None

    # -- set-up ---------------------------------------------------------------
    def start(self) -> "Session":
        import jax

        from analytics_zoo_tpu.inference.inference_model import InferenceModel
        from analytics_zoo_tpu.models.textmodels import TransformerLM
        from analytics_zoo_tpu.serving.engine import (ClusterServing,
                                                      ServingParams)
        m = self.cfg["model"]
        self.lm = TransformerLM(vocab_size=m["vocab_size"], hidden=m["n_embd"],
                                n_head=m["n_head"], n_layers=m["n_layer"],
                                max_len=m["n_positions"])
        seed = int(self.job["seed"])
        key = jax.random.fold_in(jax.random.PRNGKey(seed >> 16), seed & 0xFFFF)
        # weights on the device, from the seed, in one jitted call
        self.params = jax.block_until_ready(jax.jit(self.lm.build)(key))
        im = InferenceModel().do_load_model(self.lm, self.params, {})
        span = jax.profiler.TraceAnnotation if self.trace else None
        self.span = span
        self.queue = StampQueue(span)
        generation = dict(self.cfg["generation"])
        generation.update(self.traffic["generation"])
        self.serving = ClusterServing(im, self.queue, ServingParams(
            warmup=True, generation=generation, **self.cfg["serving"]))
        self.serving.start()
        deadline = time.monotonic() + 1100.0
        while self.serving.warmup_state().get("state") in ("pending",
                                                           "warming"):
            if time.monotonic() > deadline:
                raise RuntimeError(f"warm-up did not finish: "
                                   f"{self.serving.warmup_state()}")
            time.sleep(0.05)
        warm = self.serving.warmup_state()
        if warm.get("state") != "ready" or warm.get("failed"):
            raise RuntimeError(f"warm-up failed: {warm}")
        self.warm = {k: warm.get(k) for k in ("total", "seconds")}
        return self

    def close(self) -> None:
        if self.serving is not None:
            self.serving.shutdown(drain_s=2.0)
            self.serving = None

    # -- load -----------------------------------------------------------------
    def _send(self, run_id: str):
        vocab, seed = self.cfg["model"]["vocab_size"], int(self.job["seed"])

        def send(index: int, prompt_len: int, answer_len: int) -> None:
            ids = loadgen.token_ids(seed, index, prompt_len, vocab)
            arr = np.ascontiguousarray(ids.astype("<f4"))
            self.queue.xadd({
                "uri": f"{run_id}-{index}",
                "b64": base64.b64encode(arr).decode("ascii"),
                "dtype": "<f4", "shape": [int(prompt_len)],
                "gen": {"max_tokens": int(answer_len)}})
        return send

    def counters(self) -> dict:
        """The program's own counters, read where the work happens."""
        stats = self.serving._batcher.stats()
        hist = self.serving.registry.get(TTFT_HISTOGRAM)
        flat = {k: v for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
        for k, v in (stats.get("pool") or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                flat["pool." + k] = v
        flat["ttft_sum_s"] = float(hist.sum)
        flat["ttft_count"] = int(hist.count)
        flat["t"] = time.monotonic()
        return flat

    def measure(self, seconds: float, rate_rps: float = None,
                run_id: str = "r") -> dict:
        """Warm traffic until ``warm_finished`` requests have come back, then a
        window of ``seconds``; requests belong to it by their due time, and those
        still in flight when it closes are drained (bounded grace)."""
        import jax

        from analytics_zoo_tpu.inference import aot
        q = self.queue
        done0 = len(q.finals)
        warm_n = int(self.traffic["warm_finished"])
        gen = loadgen.LoadGenerator(
            self.traffic, self._send(run_id),
            lambda: len(q.finals) - done0 >= warm_n, rate_rps, self.span)
        q.on_final = gen.returned
        gen.start()
        deadline = time.monotonic() + 120.0
        while gen.t0 is None:
            if time.monotonic() > deadline or gen.error:
                raise RuntimeError(f"warm traffic stalled: {gen.error}")
            time.sleep(0.005)
        t0 = gen.t0
        t1 = t0 + seconds
        compiles0 = aot.COMPILE_STATS.snapshot()
        counters = {"window": [self.counters(), None]}
        samples, trace_doc, trace_dir = [], None, None
        trace_at = t0 + min(xplane.TRACE_AFTER_S, seconds / 4) \
            if self.trace else None
        while True:
            now = time.monotonic()
            if now >= t1:
                break
            if trace_at is not None and now >= trace_at:
                trace_at = None
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                    counters["trace"] = [self.counters(), None]
                    t_stop = min(time.monotonic() + xplane.TRACE_SECONDS, t1)
                    while time.monotonic() < t_stop:
                        time.sleep(SAMPLE_S)
                        samples.append(self.counters())
                    counters["trace"][1] = self.counters()
                jax.profiler.stop_trace()
                continue
            time.sleep(min(SAMPLE_S, t1 - now))
            samples.append(self.counters())
        counters["window"][1] = self.counters()
        compiles1 = aot.COMPILE_STATS.snapshot()

        # the generator keeps the load up while the window's requests finish
        grace = time.monotonic() + float(self.traffic["grace_s"])
        while time.monotonic() < grace:
            log = list(gen.log)
            if all(f"{run_id}-{e['index']}" in q.finals
                   for e in log
                   if gen.in_window(e, t1) and e["sent"] is not None):
                break
            time.sleep(0.02)
        waiting_at_end = self.serving._batcher.waiting
        gen.stop()
        q.on_final = None
        if gen.error:
            raise RuntimeError(f"load generator failed: {gen.error}")
        if trace_dir is not None:
            try:
                trace_doc = xplane.reduce_dir(trace_dir, self.job["chips"])
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)

        requests, work = [], []
        for e in gen.log:
            rid = f"{run_id}-{e['index']}"
            final = q.finals.get(rid)
            stamps = list(q.stamps.get(rid, ()))
            tokens = None
            if final is not None and "value" in final[1]:
                tokens = final[1]["value"]["tokens"]
                stamps.append((final[0], len(tokens)))
            seen = 0
            for t, n in stamps:
                work.append((t, n - seen))
                seen = n
            requests.append({**e, "in_window": gen.in_window(e, t1),
                             "stamps": stamps,
                             "tokens": tokens,
                             "ok": tokens is not None
                             and len(tokens) == e["answer_len"]})
        mine = [r for r in requests if r["in_window"]]
        failures = [{"index": r["index"], "prompt_len": r["prompt_len"],
                     "answer_len": r["answer_len"], "stamps": len(r["stamps"]),
                     "tokens": None if r["tokens"] is None else len(r["tokens"]),
                     "result": str(q.finals.get(f"{run_id}-{r['index']}",
                                                (0, None))[1])[:200]}
                    for r in mine if not r["ok"]][:5]
        return {"window": [t0, t1], "requests": requests, "work": work,
                "failures": failures,
                "counters": counters, "samples": samples, "trace": trace_doc,
                "attempted": len(mine),
                "failed": sum(1 for r in mine if not r["ok"]),
                "compiles_in_window": int(compiles1["compile_requests"]
                                          - compiles0["compile_requests"]),
                "waiting_at_end": waiting_at_end}

    # -- correctness ----------------------------------------------------------
    def check(self, requests: list) -> dict:
        """Shortest and longest prompt among the window's finished requests,
        re-scored by the plain reference; plus the engine's own health."""
        done = [r for r in requests if r["in_window"] and r["ok"]]
        if not done:
            return {"ok": False, "why": "no finished request in the window"}
        picks = {id(r): r for r in (min(done, key=lambda r: r["prompt_len"]),
                                    max(done, key=lambda r: r["prompt_len"]))}
        vocab, seed = self.cfg["model"]["vocab_size"], int(self.job["seed"])
        samples = [{"prompt": loadgen.token_ids(seed, r["index"],
                                                r["prompt_len"], vocab),
                    "tokens": r["tokens"]} for r in picks.values()]
        doc = reference.check_served(self.params, self.cfg["model"]["n_head"],
                                     samples, self.cfg["model"]["n_positions"])
        h = self.serving.health()
        pool = (h.get("generation") or {}).get("pool") or {}
        doc["engine"] = {"dead_lettered": h["dead_lettered"], "shed": h["shed"],
                         "pool_exhausted": pool.get("exhausted", 0)}
        doc["ok"] = bool(doc["ok"] and not h["dead_lettered"] and not h["shed"]
                         and not pool.get("exhausted", 0))
        return doc


def run(job: dict) -> dict:
    import jax
    session = Session(job).start()
    try:
        m = session.measure(float(job["seconds"]))
        # read before the check: the reference's arrays are not the system's
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices()[:job["chips"]])
        memory = jax.local_devices()[0].memory_stats()
        check = session.check(m["requests"])
    finally:
        session.close()
    correct = check["ok"] and m["failed"] == 0 \
        and m["compiles_in_window"] == 0 and m["attempted"] > 0
    return {**m, "correct": correct, "memory_peak_bytes": peak,
            "setup_seconds": m["window"][0] - job["t_process"],
            "chips": job["chips"], "peaks": job["peaks"],
            "config": job["config"], "traffic": job["traffic"],
            "notes": {"check": check, "warm": session.warm, "memory": memory,
                      "compiles_in_window": m["compiles_in_window"],
                      "waiting_at_end": m["waiting_at_end"],
                      "failures": m["failures"],
                      "requests_sent": len(m["requests"])}}
