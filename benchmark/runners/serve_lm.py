"""Runner ``serve_lm``: a language model of ANY class that offers the serving
plane's paged contract, served for generation.

The system is started as ``serve_generate`` starts it (``ClusterServing(
InferenceModel, InProcQueue, ServingParams(warmup=True, generation={...}))``) and
driven and measured by that runner's own code (its ``StampQueue``, ``measure`` and
``counters`` are inherited as they are); what differs is where the model comes from
and who decides ``correct``:

- ``model_class``: ``"<module>:<Class>"``.  The class is built with
  ``Class.from_config(<the configuration file>)`` and its weights with one jitted
  ``build`` from the seed, on the device, in the type the class serves them in.
- the published ``config.json`` keys stand at the TOP LEVEL of the file (where the
  benchmark's contract compares them with the source), as cut: ``reduced`` lists
  the keys that differ, ``published`` holds the source's value of each
  (``{"num_hidden_layers": 78, ...}``), and ``deployment`` states the deployment
  this chip is a share of (``text``, ``chip`` = which share, and its own counts).
- ``reference``: the module under ``benchmark/`` with ``check_served(params, file,
  samples, pad_to)``, the plain reference's verdict over what the timed path
  served: the shortest and the longest finished request of the window, teacher-
  forced, prefill and decode through the cache against the full forward.
- ``serving`` / ``generation``: as for ``serve_generate`` (the traffic file's
  ``generation`` is merged over the configuration's).

A checkout whose program lacks the class refuses the cell at once (exit 2, one
line on stderr, nothing on stdout).
"""

from __future__ import annotations

import importlib
import sys
import time

import loadgen
from runners import serve_generate


class Session(serve_generate.Session):
    def __init__(self, job: dict):
        super().__init__(job)
        # the inherited ``_send`` reads ``cfg["model"]["vocab_size"]``: here the
        # published keys ARE the file's top level
        self.cfg = dict(self.cfg, model=self.cfg)

    def start(self) -> "Session":
        import jax

        from analytics_zoo_tpu.inference.inference_model import InferenceModel
        from analytics_zoo_tpu.serving.engine import (ClusterServing,
                                                      ServingParams)
        module, _, name = self.job["config"]["model_class"].partition(":")
        try:
            cls = getattr(importlib.import_module(module), name)
        except (ImportError, AttributeError) as e:
            print(f"benchmark: refused: this checkout cannot build "
                  f"{module}:{name} ({type(e).__name__}: {e})", file=sys.stderr)
            raise SystemExit(2)
        self.lm = cls.from_config(self.job["config"])
        seed = int(self.job["seed"])
        key = jax.random.fold_in(jax.random.PRNGKey(seed >> 16), seed & 0xFFFF)
        self.params = jax.block_until_ready(jax.jit(self.lm.build)(key))
        im = InferenceModel().do_load_model(self.lm, self.params, {})
        self.span = jax.profiler.TraceAnnotation if self.trace else None
        self.queue = serve_generate.StampQueue(self.span)
        generation = dict(self.cfg["generation"])
        generation.update(self.traffic["generation"])
        self.serving = ClusterServing(im, self.queue, ServingParams(
            warmup=True, generation=generation, **self.cfg["serving"]))
        self.serving.start()
        deadline = time.monotonic() + 2400.0
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

    def check(self, requests: list) -> dict:
        """Shortest and longest prompt among the window's finished requests,
        re-scored by the configuration's plain reference; plus the engine's own
        health."""
        done = [r for r in requests if r["in_window"] and r["ok"]]
        if not done:
            return {"ok": False, "why": "no finished request in the window"}
        picks = {id(r): r for r in (min(done, key=lambda r: r["prompt_len"]),
                                    max(done, key=lambda r: r["prompt_len"]))}
        config, seed = self.job["config"], int(self.job["seed"])
        samples = [{"prompt": loadgen.token_ids(seed, r["index"],
                                                r["prompt_len"],
                                                config["vocab_size"]),
                    "tokens": r["tokens"]} for r in picks.values()]
        longest = max(len(s["prompt"]) + len(s["tokens"]) for s in samples)
        reference = importlib.import_module(config["reference"])
        doc = reference.check_served(self.params, config, samples,
                                     -(-longest // 256) * 256)
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
