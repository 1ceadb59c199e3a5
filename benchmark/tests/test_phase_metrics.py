"""CPU rehearsal of the per-layer metrics that read the generate loop's own clock
(PR 25): real ``ContinuousBatcher.stats()`` snapshots, flattened by the runner's own
``Session.counters``, read through ``run.read_metric``; and ``gaps.py`` on a timeline
whose answer is known.

    python -m pytest benchmark/tests -q
"""

import json
import math
import os
import re
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NEW = ["genloop.host_share.chat", "genloop.host_share.batch",
       "sched.boundary_host_ms.chat", "sched.boundary_host_ms.batch",
       "engine.boundary_host_ms.chat", "engine.boundary_host_ms.batch",
       "engine.intake_ms", "sched.queue_wait_ms", "sched.prefill_ms",
       "sched.first_out_ms", "sched.prefill_pad_share.chat",
       "sched.prefill_pad_share.batch"]


@pytest.fixture(scope="module")
def facts():
    """A tiny paged scheduler driven between two snapshots; the engine's phases are
    switched by hand, as its generate loop switches them around ``step()``."""
    import time

    import jax
    from analytics_zoo_tpu.common.observability import MetricsRegistry
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams, GenRequest)
    from runners import serve_generate
    lm = TransformerLM(vocab_size=64, hidden=32, n_head=2, n_layers=1, max_len=32)
    im = InferenceModel().do_load_model(lm, lm.build(jax.random.PRNGKey(0)), {})
    b = ContinuousBatcher(im, GenerationParams(
        paged=True, block_len=4, max_active_slots=2, max_tokens=8, eos_id=None,
        max_prompt_len=16, prefill_buckets=[8, 16], bucket_lens=[32],
        decode_quantum=2, stream_interval=2, prefix_cache=False))
    registry = MetricsRegistry()
    ttft = registry.histogram(serve_generate.TTFT_HISTOGRAM, "")
    session = types.SimpleNamespace(serving=types.SimpleNamespace(
        _batcher=b, registry=registry))

    def snapshot():
        return serve_generate.Session.counters(session)

    start = snapshot()
    for i, n in enumerate([3, 7, 12, 5]):
        b.clock.to("intake")
        b.submit(GenRequest(f"r{i}", (np.arange(n, dtype=np.int32) + i) % 60 + 1,
                            max_tokens=6, t_read=time.monotonic() - 0.01))
    while not b.idle:
        events = b.step()
        b.clock.to("bookkeep")
        b.clock.to("flush")
        for ev in events:
            if ev.kind == "first_token":
                ttft.observe(ev.ttft_s)
    b.clock.to("idle")
    end = snapshot()
    return {"counters": {"window": [start, end], "trace": [start, end]}}


def test_every_new_metric_reads_a_finite_number(facts):
    import run
    values = {name: run.read_metric(name, facts) for name in NEW}
    assert all(v is not None and math.isfinite(v) for v in values.values()), values
    assert all(v >= 0 for v in values.values()), values
    for cell in ("chat", "batch"):
        assert 0 < values["genloop.host_share." + cell] < 100
        # 4 requests: (3, 7) -> 2 x 8, then 12 -> 1 x 16, then 5 -> 1 x 8
        assert values["sched.prefill_pad_share." + cell] == pytest.approx(
            100 * (40 - 27) / 40)
    # the chain closes on the scheduler's own TTFT (the registry's histogram)
    assert values["sched.queue_wait_ms"] + values["sched.prefill_ms"] \
        == pytest.approx(run.read_metric("sched.ttft_ms", facts), abs=1e-6)
    assert values["engine.intake_ms"] == pytest.approx(10.0, abs=5.0)
    # phases of both kinds / boundaries, against the snapshots themselves
    start, end = facts["counters"]["window"]
    d = {k: end[k] - start[k] for k in end if k in start}
    assert values["sched.boundary_host_ms.chat"] == pytest.approx(
        1e3 * sum(d["phase_s." + p] for p in ("shed", "admit", "dispatch", "fold"))
        / d["boundaries"])
    assert values["engine.boundary_host_ms.batch"] == pytest.approx(
        1e3 * sum(d["phase_s." + p] for p in ("intake", "bookkeep", "flush"))
        / d["boundaries"])


def test_a_program_without_the_clock_reports_nothing(facts):
    """The parent commit's ``stats()`` has none of the keys: every new metric is
    left out of the line, and none raises."""
    import run
    old = {"counters": {over: [
        {k: v for k, v in snap.items() if not k.startswith("phase_")
         and k not in ("loop_s", "boundaries") and "_s_sum" not in k
         and not k.startswith("prefill_positions")
         and not k.endswith("_n")} for snap in pair]
        for over, pair in facts["counters"].items()}}
    assert "decode_steps" in old["counters"]["window"][1]
    assert [run.read_metric(name, old) for name in NEW] == [None] * len(NEW)
    # a plain run has no traced slice: the two shares that read it stay out
    plain = {"counters": {"window": facts["counters"]["window"]}}
    assert run.read_metric("genloop.host_share.chat", plain) is None
    assert run.read_metric("sched.first_out_ms", plain) is not None


def test_new_metrics_are_in_the_manifest_and_not_in_the_code():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = [m["name"] for m in manifest["per_layer"]]
    assert listed[-len(NEW):] == NEW            # appended, in the issue's order
    code = [os.path.join(BENCH, f) for f in os.listdir(BENCH) if f.endswith(".py")]
    for sub in ("runners", "readers"):
        code += [os.path.join(BENCH, sub, f)
                 for f in os.listdir(os.path.join(BENCH, sub)) if f.endswith(".py")]
    for path in code:
        text = open(path).read()
        assert not [n for n in NEW if re.search(
            r"(?<![\w.\-])" + re.escape(n) + r"(?![\w\-])", text)], path


def test_gaps_are_cut_along_the_phase_spans():
    import gaps
    # window [0, 10): busy [1, 4) and [6, 9); gaps [0, 1), [4, 6), [9, 10)
    busy = [(1.0, 4.0), (6.0, 9.0)]
    ops = [(1.0, 4.0, "fusion"), (6.0, 9.0, "paged_attention")]
    phases = [(0.5, 1.2, "dispatch"), (1.2, 3.9, "decode_wait"), (3.9, 4.5, "fold"),
              (4.5, 5.5, "flush"), (5.5, 6.2, "dispatch"), (6.2, 9.5, "decode_wait")]
    doc = gaps.attribute(busy, ops, phases, (0.0, 10.0))
    assert doc["idle_share"] == pytest.approx(0.4)
    assert doc["gaps"] == 3
    by = doc["idle_s_by_phase"]
    assert by == pytest.approx({"flush": 1.0, "dispatch": 1.0, "fold": 0.5,
                                "decode_wait": 0.5, gaps.OUTSIDE: 1.0})
    assert sum(by.values()) == pytest.approx(10.0 - doc["busy_s"])
    top = doc["longest"][0]
    assert (top["ms"], top["phase_at_middle"], top["before"], top["after"]) \
        == (pytest.approx(2000.0), "flush", "fusion", "paged_attention")
    assert top["at_s"] == pytest.approx(4.0)
    assert top["ms_by_phase"] == pytest.approx(
        {"fold": 500.0, "flush": 1000.0, "dispatch": 500.0})
    assert doc["span_s_by_phase"]["decode_wait"] == pytest.approx(2.7 + 3.3)
