"""The generate loop's own clock (PR 25): the ``PhaseClock`` partition of the
generate thread's time, the TTFT chain stamped where each step happens
(submit -> admit -> first token -> first output), the padding counters, the
profiler annotations, and the engine's side of the same clock (intake /
bookkeep / flush / idle, the ``sched_wait`` / ``prefill`` / ``first_out``
spans, the registry series).

Everything runs on the CPU on a tiny ``TransformerLM`` through the paged
scheduler — the path the benchmark's cells run.
"""

import base64
import time

import numpy as np
import pytest

from analytics_zoo_tpu.common.observability import PhaseClock
from analytics_zoo_tpu.serving.generate import (PHASE_SPAN_PREFIX, PHASES,
                                                ContinuousBatcher,
                                                GenerationParams, GenRequest)

GEN = dict(paged=True, block_len=4, max_active_slots=2, max_tokens=8,
           eos_id=None, max_prompt_len=16, prefill_buckets=[8, 16],
           bucket_lens=[32], decode_quantum=2, stream_interval=2)


@pytest.fixture(scope="module")
def im():
    import jax
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    lm = TransformerLM(vocab_size=64, hidden=32, n_head=2, n_layers=1,
                       max_len=32)
    return InferenceModel().do_load_model(
        lm, lm.build(jax.random.PRNGKey(0)), {})


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
def driven(im):
    """One batcher driven through admissions, decode and finishes: five
    requests on two slots, so three of them wait for a slot."""
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    t_start = b.clock.totals()          # the clock starts at construction
    t0 = time.monotonic() - sum(t_start[0].values())
    lengths = [3, 7, 12, 5, 9]
    for i, n in enumerate(lengths):
        assert b.submit(GenRequest(f"r{i}", _prompt(n, i), max_tokens=6,
                                   t_read=time.monotonic() - 0.25))
    events = _drive(b)
    return b, events, t0, lengths


# -- the clock alone ------------------------------------------------------------

def test_phase_clock_partitions_a_fake_timeline():
    now = [10.0]
    clock = PhaseClock(("a", "b", "c"), clock=lambda: now[0])
    now[0] = 11.0
    clock.to("b")
    now[0] = 11.5
    with clock.phase("c"):
        now[0] = 13.5
        assert clock.current == "c"
    assert clock.current == "b"         # the with form returns to its outer
    now[0] = 14.0
    seconds, counts = clock.totals()
    assert seconds == {"a": 1.0, "b": 1.0, "c": 2.0}     # b: 0.5 + 0.5 open
    assert counts == {"a": 1, "b": 1, "c": 1}            # completed visits
    assert sum(seconds.values()) == now[0] - 10.0
    with pytest.raises(KeyError):
        clock.to("nope")
    assert clock.current == "b"         # a refused switch changes nothing


def test_phase_clock_with_form_survives_an_exception():
    clock = PhaseClock(("outer", "inner"))
    with pytest.raises(RuntimeError):
        with clock.phase("inner"):
            raise RuntimeError("boom")
    assert clock.current == "outer"


# -- the scheduler ---------------------------------------------------------------

def test_phases_partition_the_thread_time(driven):
    b, _, t0, _ = driven
    s = b.stats()
    elapsed = time.monotonic() - t0
    assert set(PHASES) == {k[len("phase_s."):] for k in s
                           if k.startswith("phase_s.")}
    total = sum(s["phase_s." + p] for p in PHASES)
    assert total == pytest.approx(s["loop_s"], rel=1e-9)
    assert s["loop_s"] == pytest.approx(elapsed, rel=0.02)
    # the scheduler's own phases all ran; the engine's never did here
    for p in ("shed", "admit", "prefill_wait", "dispatch", "decode_wait",
              "fold"):
        assert s["phase_s." + p] > 0 and s["phase_n." + p] > 0, p
    for p in ("intake", "bookkeep", "flush"):
        assert s["phase_s." + p] == 0 and s["phase_n." + p] == 0, p


def test_one_decode_wait_per_boundary(driven):
    b, _, _, _ = driven
    s = b.stats()
    assert s["boundaries"] > 0
    assert s["phase_n.decode_wait"] == s["boundaries"]
    assert s["phase_n.dispatch"] == s["boundaries"]
    assert s["decode_steps"] == s["boundaries"] * GEN["decode_quantum"]


def test_chain_sums_to_ttft_per_request(driven):
    b, events, _, lengths = driven
    first = {e.rid: e for e in events if e.kind == "first_token"}
    assert len(first) == len(lengths)
    for e in first.values():
        t_submit = e.t_first - e.ttft_s
        queue_wait, prefill = e.t_admit - t_submit, e.t_first - e.t_admit
        assert queue_wait >= 0 and prefill > 0
        assert queue_wait + prefill == pytest.approx(e.ttft_s, abs=1e-6)
    s = b.stats()
    assert s["queue_wait_n"] == s["prefill_n"] == s["admitted"] == len(lengths)
    assert s["queue_wait_s_sum"] + s["prefill_s_sum"] == pytest.approx(
        sum(e.ttft_s for e in first.values()), abs=1e-6)
    # every request has emitted by now: the chain's last link closed for all
    assert s["first_out_n"] == s["admitted"]
    outs = [e for e in events if e.t_out is not None]
    assert sorted(e.rid for e in outs) == sorted(first)     # once a request
    assert all(e.kind in ("partial", "finish") for e in outs)
    assert all(e.t_out >= e.t_first == first[e.rid].t_first for e in outs)
    assert s["first_out_s_sum"] == pytest.approx(
        sum(e.t_out - e.t_first for e in outs), abs=1e-6)
    # read -> submit, as the caller stamped it
    assert s["intake_n"] == len(lengths)
    assert s["intake_s_sum"] == pytest.approx(0.25 * len(lengths), abs=0.05)


def test_a_request_without_a_slot_waits_a_boundary(im):
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    for i in range(2):                       # fill both slots
        b.submit(GenRequest(f"busy{i}", _prompt(5, i), max_tokens=8))
    b.step()
    assert b.active == 2 and b.waiting == 0
    late = GenRequest("late", _prompt(5, 9), max_tokens=2)
    b.submit(late)
    t0 = time.monotonic()
    b.step()                                 # every slot busy: it stays
    boundary_s = time.monotonic() - t0
    assert b.waiting == 1
    events = _drive(b)
    ev = next(e for e in events if e.kind == "first_token" and e.rid == "late")
    assert ev.t_admit - late.t_submit >= boundary_s
    assert late.t_admit == ev.t_admit        # the stamp of the pop that kept it


def test_prefill_padding_counters(im):
    b = ContinuousBatcher(im, GenerationParams(
        **{**GEN, "max_active_slots": 4}))
    for i, n in enumerate([3, 5, 7]):        # one batch: bucket 4 x 8
        b.submit(GenRequest(f"a{i}", _prompt(n, i), max_tokens=2))
    b.step()
    s = b.stats()
    assert (s["prefill_positions_real"], s["prefill_positions_padded"]) \
        == (15, 32)
    _drive(b)
    # no prefix in common with the first three (a shared one would be skipped)
    b.submit(GenRequest("b0", _prompt(9, 30), max_tokens=2))   # bucket 1 x 16
    _drive(b)
    s = b.stats()
    assert (s["prefill_positions_real"], s["prefill_positions_padded"]) \
        == (24, 48)


class _Recorder:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that logs."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        rec = self

        class Span:
            def __enter__(self):
                rec.log.append(("enter", name))

            def __exit__(self, *exc):
                rec.log.append(("exit", name))
        return Span()


def test_annotations_are_named_paired_and_never_overlap(im):
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    assert b.clock._annotate is not None      # the profiler's, by default
    rec = _Recorder()
    b.clock = PhaseClock(PHASES, annotate=rec, prefix=PHASE_SPAN_PREFIX)
    for i in range(3):
        b.submit(GenRequest(f"r{i}", _prompt(4 + i, i), max_tokens=4))
    _drive(b)
    names = {name for _, name in rec.log}
    assert names <= {PHASE_SPAN_PREFIX + p for p in PHASES}
    assert {"zoo.gen.admit", "zoo.gen.prefill_wait", "zoo.gen.dispatch",
            "zoo.gen.decode_wait", "zoo.gen.fold"} <= names
    open_ = None
    for what, name in rec.log:               # one span open at any time
        if what == "enter":
            assert open_ is None, (open_, name)
            open_ = name
        else:
            assert open_ == name
            open_ = None
    assert open_ is not None                 # the current phase stays open


def test_warm_up_does_not_touch_the_clock(im):
    b = ContinuousBatcher(im, GenerationParams(**GEN))
    rec = _Recorder()
    b.clock = PhaseClock(PHASES, annotate=rec, prefix=PHASE_SPAN_PREFIX)
    assert b.warm()["failed"] == 0
    assert rec.log == [] and b.clock.current == PHASES[0]
    assert sum(b.clock.totals()[1].values()) == 0


# -- the engine ------------------------------------------------------------------

def _enqueue(queue, rid, tokens, max_tokens):
    arr = np.ascontiguousarray(np.asarray(tokens, "<f4"))
    queue.xadd({"uri": rid, "b64": base64.b64encode(arr).decode("ascii"),
                "dtype": "<f4", "shape": list(arr.shape),
                "gen": {"max_tokens": max_tokens}})


@pytest.fixture(scope="module")
def served(im):
    from analytics_zoo_tpu.serving.client import OutputQueue
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue
    q = InProcQueue()
    serving = ClusterServing(im, q, ServingParams(
        max_batch=8, max_wait_ms=2.0, generation=GEN))
    serving.start()
    try:
        rids = [f"e{i}" for i in range(5)]
        for i, rid in enumerate(rids):
            _enqueue(q, rid, _prompt(3 + 2 * i, i), 6)
        res = OutputQueue(q).query_many(rids, timeout_s=60.0)
        assert all("value" in res[r] for r in rids), res
        time.sleep(0.25)                     # the loop goes back to idle
        yield serving, rids
    finally:
        serving.shutdown(drain_s=2.0)


def test_engine_phases_and_health(served):
    serving, rids = served
    s = serving.health()["generation"]
    for p in ("idle", "intake", "bookkeep", "flush", "admit", "decode_wait"):
        assert s["phase_s." + p] > 0 and s["phase_n." + p] > 0, p
    assert sum(s["phase_s." + p] for p in PHASES) == pytest.approx(
        s["loop_s"], rel=1e-9)
    assert s["intake_n"] == s["queue_wait_n"] == s["first_out_n"] == len(rids)
    assert s["intake_s_sum"] > 0


def test_engine_spans_come_from_the_stamps(served):
    serving, rids = served
    spans = serving.tracer.spans()
    for rid in rids:
        mine = {s["stage"]: s for s in spans if s["uri"] == rid
                and s["stage"] in ("sched_wait", "prefill", "first_out")}
        assert set(mine) == {"sched_wait", "prefill", "first_out"}, rid
        wait, prefill, out = (mine[k] for k in ("sched_wait", "prefill",
                                                "first_out"))
        # one chain: each span starts where the one before it ended
        assert wait["ts"] + wait["dur_s"] == pytest.approx(prefill["ts"],
                                                           abs=1e-9)
        assert prefill["ts"] + prefill["dur_s"] == pytest.approx(out["ts"],
                                                                 abs=1e-9)
        assert wait["ts"] <= prefill["ts"] <= out["ts"]
        assert prefill["dur_s"] > 0
    # the prefill spans carry the stamped interval, not the handling time
    total = sum(s["dur_s"] for s in spans if s["stage"] == "prefill"
                and s["uri"] in rids)
    assert total == pytest.approx(
        serving.health()["generation"]["prefill_s_sum"], abs=1e-6)


def test_phase_series_in_the_prometheus_text(served):
    serving, _ = served
    text = serving.prom_metrics()
    assert "# TYPE serving_generate_phase_seconds_total gauge" in text
    values = {}
    for line in text.splitlines():
        if line.startswith("serving_generate_phase_seconds_total{"):
            label, value = line.split("} ")
            values[label.split('phase="')[1].rstrip('"')] = float(value)
    assert set(values) == set(PHASES)
    assert values["decode_wait"] > 0 and values["idle"] > 0
