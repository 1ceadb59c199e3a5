"""Elastic serving (PR 10 tentpole): the closed-loop autoscaler policy as a
PURE decision function (golden signal tables -> actions, fake clock, no
sleeps or live engines), live engine knob retune, delivery-count poison
parking, cross-replica fleet aggregation (JSON + merged Prometheus), the
single-port load-balancing front door (re-routing across replica death and
scale events), the scale-down drain that must NOT close shared admission,
per-leaf buffer donation, and the slow-marked chaos acceptance A/B (10x
load swing + replica SIGKILL, autoscale on vs off)."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.serving.autoscaler import (Action, Autoscaler,
                                                  AutoscalerParams,
                                                  AutoscalerPolicy,
                                                  EngineFleet, FleetSignals,
                                                  ManagerFleet)
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
from analytics_zoo_tpu.serving.queues import (FileQueue, InProcQueue,
                                              RedisQueue)

from test_serving_availability import FakeRedis

DIM, NCLS = 3, 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.autoscale


def _im(concurrent=8, max_batch=1024):
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    model = Sequential()
    model.add(Dense(NCLS, input_shape=(DIM,), activation="softmax"))
    model.init_weights()
    return InferenceModel(supported_concurrent_num=concurrent,
                          max_batch=max_batch) \
        .do_load_model(model, model._params, model._state)


def _serving(queue, im=None, **params):
    defaults = dict(batch_size=4, poll_timeout_s=0.02, write_backoff_s=0.01,
                    worker_backoff_s=0.01)
    defaults.update(params)
    return ClusterServing(im or _im(), queue,
                          params=ServingParams(**defaults))


def _sig(**kw):
    """Signal shorthand for the decision tables: a healthy 2-replica fleet
    with knob room unless overridden."""
    base = dict(queue_depth=0, pending=0, replicas=2, desired=2,
                served_total=0, shed_total=0, quarantined_total=0,
                reclaimed_total=0, e2e_p99_ms=None,
                heartbeat_ages={"r0": 0.1, "r1": 0.1},
                max_batch=8, max_batch_ceiling=64,
                inflight_batches=2, inflight_ceiling=8,
                preprocess_workers=1)
    base.update(kw)
    return FleetSignals(**base)


def _kinds(actions):
    return [a.kind for a in actions]


# -- golden decision tables (pure policy, fake clock) ---------------------------

def test_policy_dead_band_holds():
    """Signals between the hysteresis bands produce NO action — and reset
    both dwell timers, so a borderline workload never accumulates credit."""
    pol = AutoscalerPolicy(AutoscalerParams(
        slo_p99_ms=1000, dwell_up_s=1.0, dwell_down_s=2.0, knob_dwell_s=0.5))
    # p99 at 50% of SLO, backlog mid-band: neither overload nor underload
    mid = _sig(e2e_p99_ms=500.0, queue_depth=10)
    for t in (0.0, 1.0, 2.0, 5.0, 10.0):
        assert pol.decide(mid, t) == []
    # alternating overload/mid never fires the dwell
    hot = _sig(e2e_p99_ms=900.0, queue_depth=200)
    assert _kinds(pol.decide(hot, 11.0)) == ["retune_up"]   # fast tier only
    assert pol.decide(mid, 11.5) == []                      # dwell reset
    assert _kinds(pol.decide(hot, 12.1)) == ["retune_up"]
    assert pol.decide(mid, 12.6) == []
    # no scale_up ever fired: overload was never continuous for dwell_up_s
    assert pol._last_scale == float("-inf")


def test_policy_dwell_then_scale_up_bounded():
    """Sustained overload scales up only after dwell_up_s, stepping at most
    max_step and never past max_replicas; each step re-arms the dwell."""
    pol = AutoscalerPolicy(AutoscalerParams(
        slo_p99_ms=1000, dwell_up_s=1.0, knob_dwell_s=100.0,  # knobs quiet
        max_step=2, max_replicas=5))
    hot = _sig(e2e_p99_ms=2000.0, queue_depth=500, max_batch=64,
               max_batch_ceiling=64, inflight_batches=8, inflight_ceiling=8)
    assert pol.decide(hot, 0.0) == []                 # dwell starts
    assert pol.decide(hot, 0.5) == []                 # still dwelling
    acts = pol.decide(hot, 1.1)                       # dwell met
    assert _kinds(acts) == ["scale_up"] and acts[0].target == 4  # 2 + 2
    hot4 = _sig(e2e_p99_ms=2000.0, queue_depth=500, replicas=4, desired=4,
                max_batch=64, max_batch_ceiling=64,
                inflight_batches=8, inflight_ceiling=8)
    assert pol.decide(hot4, 1.5) == []                # dwell re-armed
    acts = pol.decide(hot4, 2.2)
    assert _kinds(acts) == ["scale_up"]
    assert acts[0].target == 5                        # capped at max_replicas
    hot5 = _sig(e2e_p99_ms=2000.0, queue_depth=500, replicas=5, desired=5,
                max_batch=64, max_batch_ceiling=64,
                inflight_batches=8, inflight_ceiling=8)
    assert pol.decide(hot5, 3.5) == []                # at the ceiling: hold


def test_policy_scale_down_needs_dwell_and_cooldown():
    """Scale-down requires BOTH continuous underload for dwell_down_s and
    scale_down_cooldown_s since the last scale event — an upscale burst is
    never immediately given back."""
    pol = AutoscalerPolicy(AutoscalerParams(
        slo_p99_ms=1000, dwell_up_s=0.5, dwell_down_s=2.0,
        scale_down_cooldown_s=10.0, knob_dwell_s=100.0,
        max_step=2, min_replicas=1, max_replicas=8))
    hot = _sig(e2e_p99_ms=2000.0, queue_depth=500, max_batch=64,
               max_batch_ceiling=64, inflight_batches=8, inflight_ceiling=8)
    pol.decide(hot, 0.0)
    assert _kinds(pol.decide(hot, 0.6)) == ["scale_up"]   # t=0.6: scaled
    idle = _sig(replicas=4, desired=4, e2e_p99_ms=50.0)
    # underload from t=1 on; dwell met at t=3, but cooldown runs to t=10.6
    for t in (1.0, 3.5, 8.0):
        assert pol.decide(idle, t) == []
    acts = pol.decide(idle, 10.7)
    assert _kinds(acts) == ["scale_down"] and acts[0].target == 2
    idle1 = _sig(replicas=1, desired=1, e2e_p99_ms=50.0,
                 heartbeat_ages={"r0": 0.1})
    pol2 = AutoscalerPolicy(AutoscalerParams(min_replicas=1,
                                             dwell_down_s=0.1,
                                             scale_down_cooldown_s=0.0))
    pol2.decide(idle1, 0.0)
    assert pol2.decide(idle1, 1.0) == []              # at the floor: hold


def test_policy_knob_ladder_and_relax():
    """Fast tier: max_batch doubles first (within the pow-2 ceiling), then
    inflight steps, then preprocess_workers — the last only when preprocess
    is the measured long pole; underload relaxes toward the baseline and
    never below it."""
    pol = AutoscalerPolicy(AutoscalerParams(
        slo_p99_ms=1000, knob_dwell_s=1.0, dwell_up_s=100.0))  # no topology
    hot = _sig(e2e_p99_ms=2000.0, queue_depth=500,
               max_batch=16, max_batch_ceiling=32)
    acts = pol.decide(hot, 0.0)
    assert _kinds(acts) == ["retune_up"]
    assert acts[0].knobs == {"max_batch": 32}
    assert pol.decide(hot, 0.5) == []                 # knob dwell
    hot2 = _sig(e2e_p99_ms=2000.0, queue_depth=500,
                max_batch=32, max_batch_ceiling=32,
                inflight_batches=2, inflight_ceiling=4)
    acts = pol.decide(hot2, 1.5)
    assert acts[0].knobs == {"inflight_batches": 3}
    # preprocess nudge ONLY when preprocess >= predict p99
    hot3 = _sig(e2e_p99_ms=2000.0, queue_depth=500,
                max_batch=32, max_batch_ceiling=32,
                inflight_batches=4, inflight_ceiling=4,
                preprocess_p99_ms=900.0, predict_p99_ms=100.0,
                preprocess_workers=1)
    acts = pol.decide(hot3, 3.0)
    assert acts[0].knobs == {"preprocess_workers": 2}
    hot4 = _sig(e2e_p99_ms=2000.0, queue_depth=500,
                max_batch=32, max_batch_ceiling=32,
                inflight_batches=4, inflight_ceiling=4,
                preprocess_p99_ms=100.0, predict_p99_ms=900.0)
    assert pol.decide(hot4, 4.5) == []                # ladder exhausted
    # relax: back toward the FIRST-SEEN baseline (max_batch=16), never below
    idle = _sig(e2e_p99_ms=10.0, max_batch=32, max_batch_ceiling=32)
    acts = pol.decide(idle, 6.0)
    assert acts[0].kind == "retune_down"
    assert acts[0].knobs == {"max_batch": 16}
    idle2 = _sig(e2e_p99_ms=10.0, max_batch=16, max_batch_ceiling=32)
    assert pol.decide(idle2, 7.5) == []               # at baseline: hold


def test_policy_baseline_skips_empty_fleet_ticks():
    """Review regression: ticks BEFORE any replica reports (manager
    replicas spend seconds in model load; signals then carry placeholder
    knob defaults) must not become the relax baseline — otherwise idle
    periods ratchet a configured max_batch=64 down to the default 4."""
    pol = AutoscalerPolicy(AutoscalerParams(
        slo_p99_ms=1000, knob_dwell_s=0.1, dwell_up_s=100.0))
    empty = FleetSignals(replicas=0, desired=2, max_batch=4,
                        inflight_batches=2, preprocess_workers=1)
    assert pol.decide(empty, 0.0) == []            # nothing to baseline on
    assert pol._baseline_knobs is None
    real = _sig(queue_depth=10, max_batch=64, max_batch_ceiling=64)
    pol.decide(real, 1.0)
    assert pol._baseline_knobs["max_batch"] == 64  # the REAL config
    idle = _sig(e2e_p99_ms=10.0, max_batch=64, max_batch_ceiling=64)
    assert pol.decide(idle, 2.0) == []             # at baseline: no relax


def test_policy_shed_rate_is_overload_evidence():
    """A rising cumulative shed counter (differentiated into a rate between
    ticks) classifies as overload even with healthy p99/backlog, and a
    FALLING counter (a replaced member leaving the sum) clamps to zero
    instead of poisoning the rate."""
    pol = AutoscalerPolicy(AutoscalerParams(
        slo_p99_ms=1000, knob_dwell_s=0.1, dwell_up_s=100.0))
    assert pol.decide(_sig(shed_total=100), 0.0) == []    # no prev: rate 0
    acts = pol.decide(_sig(shed_total=150), 1.0)          # 50 sheds/s
    assert _kinds(acts) == ["retune_up"]
    assert pol.decide(_sig(shed_total=20), 2.0) == []     # negative delta


def test_policy_stale_heartbeat_replace_with_cooldown():
    """A replica whose heartbeat age passes heartbeat_stale_s is replaced
    exactly once per replace_cooldown_s, regardless of the load bands."""
    pol = AutoscalerPolicy(AutoscalerParams(
        heartbeat_stale_s=5.0, replace_cooldown_s=10.0, knob_dwell_s=100.0))
    # queue_depth=10 keeps the load signals in the dead band so ONLY the
    # heartbeat path can act
    ok = _sig(queue_depth=10, heartbeat_ages={"r0": 0.1, "r1": 1.0})
    assert pol.decide(ok, 0.0) == []
    dead = _sig(queue_depth=10, heartbeat_ages={"r0": 0.1, "r1": 12.0})
    acts = pol.decide(dead, 1.0)
    assert _kinds(acts) == ["replace_replica"] and acts[0].target == "r1"
    assert pol.decide(dead, 5.0) == []                # replace cooldown
    acts = pol.decide(dead, 11.5)                     # cooldown elapsed,
    assert _kinds(acts) == ["replace_replica"]        # still stale: retry
    both = _sig(queue_depth=10, heartbeat_ages={"r0": 30.0, "r1": 30.0})
    acts = AutoscalerPolicy(AutoscalerParams(
        heartbeat_stale_s=5.0, knob_dwell_s=100.0)).decide(both, 0.0)
    assert _kinds(acts) == ["replace_replica", "replace_replica"]
    assert [a.target for a in acts] == ["r0", "r1"]


# -- controller runtime: metrics + actuation ------------------------------------

class _ScriptedFleet:
    """Signal script + actuator recorder for Autoscaler runtime tests."""

    def __init__(self, signals):
        self._signals = list(signals)
        self.calls = []
        self.desired = signals[0].desired

    def signals(self):
        return self._signals.pop(0) if len(self._signals) > 1 \
            else self._signals[0]

    def scale_to(self, n):
        self.calls.append(("scale_to", n))
        self.desired = n

    def retune(self, **knobs):
        self.calls.append(("retune", knobs))

    def replace(self, rid):
        self.calls.append(("replace", rid))


def test_autoscaler_runtime_metrics_and_decision_log():
    """Every action increments autoscaler_decisions_total{action=}, moves
    the target gauges, and lands in the decision log — the observability
    contract `manager metrics` exposes."""
    hot = _sig(e2e_p99_ms=2000.0, queue_depth=500, max_batch=8,
               max_batch_ceiling=16,
               heartbeat_ages={"r0": 0.1, "r1": 99.0})
    fleet = _ScriptedFleet([hot])
    scaler = Autoscaler(fleet, params=AutoscalerParams(
        slo_p99_ms=1000, dwell_up_s=1.0, knob_dwell_s=0.5,
        heartbeat_stale_s=5.0, max_step=2, max_replicas=8))
    acts = scaler.tick(now=0.0)       # replace + retune (dwell not yet met)
    assert sorted(_kinds(acts)) == ["replace_replica", "retune_up"]
    acts = scaler.tick(now=1.5)       # dwell met: scale_up (knob dwell gates)
    assert "scale_up" in _kinds(acts)
    assert ("scale_to", 4) in fleet.calls
    assert ("replace", "r1") in fleet.calls
    assert ("retune", {"max_batch": 16}) in fleet.calls
    reg = scaler.registry
    dec = reg.get("autoscaler_decisions_total")
    assert dec.labels(action="scale_up").value == 1
    assert dec.labels(action="replace_replica").value == 1
    assert dec.labels(action="retune_up").value >= 1
    assert dec.labels(action="scale_down").value == 0   # materialized at 0
    assert reg.get("autoscaler_target_replicas").value == 4
    assert reg.get("autoscaler_observed_p99_ms").value == 2000.0
    log = scaler.decisions()
    assert any(e["action"] == "scale_up" and e["target"] == 4 for e in log)
    assert all("reason" in e for e in log)
    prom = reg.to_prometheus()
    assert 'autoscaler_decisions_total{action="scale_up"} 1' in prom
    snap = scaler.snapshot()
    assert snap["decisions"] and "autoscaler_decisions_total" in snap["prom"]


# -- live engine retune ---------------------------------------------------------

def test_retune_validates_and_applies_at_batch_boundary(ctx):
    """retune() clamps to the pow-2 ladder / model ceilings, the staged
    knobs land at the preprocess loop's next batch (including the write
    queue resize), and records keep serving across the nudge."""
    q = InProcQueue()
    im = _im(concurrent=3)
    s = _serving(q, im=im, max_batch=8).start()
    try:
        applied = s.retune(max_batch=100, inflight_batches=99,
                           preprocess_workers=500, max_wait_ms=-5)
        assert applied == {"max_batch": 64, "inflight_batches": 3,
                           "preprocess_workers": 32, "max_wait_ms": 0.0}
        cin = InputQueue(q)
        for i in range(8):
            cin.enqueue_tensor(f"r{i}", np.ones(DIM, np.float32))
        out = OutputQueue(q)
        res = out.query_many([f"r{i}" for i in range(8)], timeout_s=30)
        assert all(r is not None and not OutputQueue.is_error(r)
                   for r in res.values())
        # the preprocess worker applied the staged knobs on its first batch
        assert s.params.max_batch == 64
        assert s.params.inflight_batches == 3
        assert s._writeq.maxsize == 3
        assert s.params.preprocess_workers == 32
        k = s.knobs()
        assert k["max_batch"] == 64 and k["inflight_ceiling"] == 3
        assert s.health()["knobs"]["max_batch"] == 64
    finally:
        s.shutdown()


# -- delivery-count poison parking ----------------------------------------------

@pytest.mark.parametrize("kind", ["inproc", "file", "redis"])
def test_max_deliveries_parks_poison_pill(kind, tmp_path, ctx):
    """A record redelivered past ServingParams.max_deliveries is parked to
    the dead-letter queue with a max-deliveries-exceeded error (claim
    released, client unblocked) instead of looping through reclaim
    forever."""
    if kind == "inproc":
        q = InProcQueue()
    elif kind == "file":
        q = FileQueue(str(tmp_path / "q"))
    else:
        q = RedisQueue(client=FakeRedis())
    cin = InputQueue(q)
    cin.enqueue_tensor("pill", np.ones(DIM, np.float32))
    trace = cin.last_trace_id
    # a doomed consumer claims it and dies without acking, twice
    assert len(q.read_batch(10, timeout_s=0.01)) == 1   # delivery 1
    time.sleep(0.03)
    q.consumer = "doomed-2"
    assert [r for r, _, _ in q.reclaim(0.02)] == ["pill"]  # delivery 2
    time.sleep(0.03)
    # the engine's sweep sees delivery 3 > max_deliveries=2: park it
    s = _serving(q, lease_s=0.02, reclaim_interval_s=0.0, max_deliveries=2)
    served = s.serve_once()
    assert served == 0 and s.dead_lettered == 1
    res = q.get_result("pill")
    assert OutputQueue.is_error(res)
    assert "max-deliveries-exceeded" in res["error"]
    assert res.get("trace_id") == trace                # lineage survives
    dead = q.dead_letters()
    assert len(dead) == 1
    assert "max-deliveries-exceeded" in dead[0]["error"]
    assert dead[0]["record"]["deliveries"] == 3        # count rides the entry
    assert q.pending_count() == 0                      # claim released
    # quarantine is attributed to the reclaim stage in the metrics
    reg = s.registry.get("serving_quarantined_total")
    assert reg.labels(stage="reclaim").value == 1
    # and a sweep with max_deliveries disabled would have redelivered: the
    # SAME setup with the cap off serves the record normally
    q2 = InProcQueue()
    InputQueue(q2).enqueue_tensor("ok", np.ones(DIM, np.float32))
    q2.read_batch(10, timeout_s=0.01)
    time.sleep(0.03)
    s2 = _serving(q2, lease_s=0.02, reclaim_interval_s=0.0,
                  max_deliveries=0)
    while s2.serve_once():
        pass
    assert not OutputQueue.is_error(q2.get_result("ok"))


# -- scale-down drain must not close shared admission ---------------------------

def test_scale_down_drain_keeps_shared_admission_open(ctx):
    """Regression: a replica draining for SCALE-DOWN
    (shutdown(close_admission=False) — what EngineFleet and the manager's
    SIGUSR1 retire path use) flushes its in-flight work but leaves the
    shared queue accepting records for the survivors.  The PR 5 scale path
    closed admission on the shared backend and cut off the whole fleet."""
    q = InProcQueue()
    im = _im()
    fleet = EngineFleet(lambda rid: _serving(q, im=im, replica_id=rid)
                        .start(), q, initial=2, drain_s=5.0)
    try:
        cin = InputQueue(q)
        out = OutputQueue(q)
        for i in range(6):
            cin.enqueue_tensor(f"a{i}", np.ones(DIM, np.float32))
        fleet.scale_to(1)              # retires one replica, drained
        # the shared queue still takes traffic and the survivor serves it
        for i in range(6):
            cin.enqueue_tensor(f"b{i}", np.ones(DIM, np.float32))
        uris = [f"a{i}" for i in range(6)] + [f"b{i}" for i in range(6)]
        res = out.query_many(uris, timeout_s=30)
        assert all(r is not None and not OutputQueue.is_error(r)
                   for r in res.values()), res
        assert q.health()["admission_open"] is True
        assert len(fleet.engines()) == 1
        # replace() also leaves admission open (hard-stop + respawn)
        victim = fleet.engines()[0].replica_id
        fleet.replace(victim)
        cin.enqueue_tensor("c0", np.ones(DIM, np.float32))
        assert not OutputQueue.is_error(out.query("c0", timeout_s=30))
    finally:
        fleet.shutdown()


# -- fleet aggregation (manager metrics --all-replicas / ManagerFleet) ----------

def _health_doc(rid, served, shed=0, depth=5, pending=2, p99=100.0,
                hb=0.1, running=True, knobs=None):
    return {"running": running, "replica_id": rid, "heartbeat_age_s": hb,
            "total_records": served, "dead_lettered": 0, "shed": shed,
            "reclaimed": 1, "duplicates": 0,
            "workers": {"serving-preprocess": {"restart_count": 1}},
            "queue": {"depth": depth, "pending": pending, "dead_letters": 3},
            "knobs": knobs or {"max_batch": 8, "max_batch_ceiling": 64,
                               "inflight_batches": 2, "inflight_ceiling": 8,
                               "preprocess_workers": 1},
            "stages": {"e2e": {"count": served, "p50_ms": p99 / 2,
                               "p99_ms": p99},
                       "preprocess": {"p99_ms": 5.0},
                       "predict": {"p99_ms": 50.0}}}


def test_fleet_aggregation_sums_and_maxes(tmp_path):
    """aggregate_health: cumulative counters SUM across replicas, the
    shared queue's depth/pending take the MAX (not xN), heartbeats stay
    per-replica, p99 is the conservative max; fleet_metrics carries the
    per-replica breakdown; snapshot-sourced docs age by their staleness."""
    from analytics_zoo_tpu.serving import fleet as _fleet
    docs = {0: _health_doc("replica-0", 100, depth=7, p99=120.0),
            1: _health_doc("replica-1", 40, shed=3, depth=6, hb=9.0,
                           running=False, p99=300.0)}
    agg = _fleet.aggregate_health(docs)
    assert agg["served"] == 140 and agg["shed"] == 3
    assert agg["reclaimed"] == 2 and agg["restarts"] == 2
    assert agg["queue_depth"] == 7 and agg["pending"] == 2   # max, not sum
    assert agg["replicas_total"] == 2 and agg["replicas_alive"] == 1
    assert agg["heartbeat_ages"] == {"replica-0": 0.1, "replica-1": 9.0}
    assert agg["e2e_p99_ms"] == 300.0
    assert agg["knobs"]["max_batch"] == 8
    fm = _fleet.fleet_metrics(docs)
    assert fm["served"] == 140 and fm["latency_ms"]["p99"] == 300.0
    assert fm["per_replica"]["replica-1"]["shed"] == 3
    assert fm["per_replica"]["replica-1"]["running"] is False
    # file-fallback path: stale snapshots age the heartbeat
    pidfile = str(tmp_path / "cs.pid")
    with open(pidfile + ".replicas", "w") as f:
        f.write("2")
    old = dict(_health_doc("replica-0", 10, hb=0.05), ts=time.time() - 30)
    with open(pidfile + ".r0.health.json", "w") as f:
        json.dump(old, f)
    fresh = dict(_health_doc("replica-1", 20, hb=0.05), ts=time.time())
    with open(pidfile + ".r1.health.json", "w") as f:
        json.dump(fresh, f)
    docs = _fleet.replica_docs(pidfile)
    assert set(docs) == {0, 1}
    assert docs[0]["heartbeat_age_s"] >= 29.0     # aged by staleness
    assert docs[1]["heartbeat_age_s"] < 5.0
    # ManagerFleet builds controller signals from the same docs
    mf = ManagerFleet(pidfile)
    sig = mf.signals()
    assert sig.served_total == 30 and sig.desired == 2
    assert sig.heartbeat_ages["replica-0"] >= 29.0
    assert sig.max_batch == 8 and sig.max_batch_ceiling == 64
    # ... and actuates through the supervisor's files
    mf.scale_to(5)
    assert mf.desired == 5
    mf.retune(max_batch=16)
    mf.retune(inflight_batches=4)
    with open(mf.knobs_path) as f:
        assert json.load(f) == {"max_batch": 16, "inflight_batches": 4}


def test_merge_prometheus_sums_counters_maxes_shared_gauges():
    from analytics_zoo_tpu.serving.fleet import merge_prometheus
    a = "\n".join([
        "# HELP serving_records_total Records served",
        "# TYPE serving_records_total counter",
        "serving_records_total 100",
        "# HELP serving_queue_depth Records waiting",
        "# TYPE serving_queue_depth gauge",
        "serving_queue_depth 7",
        "# HELP serving_e2e_seconds e2e",
        "# TYPE serving_e2e_seconds histogram",
        'serving_e2e_seconds_bucket{le="0.1"} 90',
        'serving_e2e_seconds_bucket{le="+Inf"} 100',
        "serving_e2e_seconds_sum 4.5",
        "serving_e2e_seconds_count 100",
        "# HELP serving_heartbeat_age_seconds hb",
        "# TYPE serving_heartbeat_age_seconds gauge",
        'serving_heartbeat_age_seconds{replica="r0"} 0.2',
    ]) + "\n"
    b = a.replace("100", "40").replace("90", "35").replace("4.5", "2.0") \
         .replace("serving_queue_depth 7", "serving_queue_depth 6") \
         .replace('replica="r0"} 0.2', 'replica="r1"} 0.5')
    merged = merge_prometheus([a, b])
    assert "serving_records_total 140" in merged
    assert "serving_queue_depth 7" in merged          # shared gauge: max
    assert 'serving_e2e_seconds_bucket{le="0.1"} 125' in merged
    assert 'serving_e2e_seconds_bucket{le="+Inf"} 140' in merged
    assert "serving_e2e_seconds_sum 6.5" in merged
    assert "serving_e2e_seconds_count 140" in merged
    # per-replica series pass through side by side
    assert 'serving_heartbeat_age_seconds{replica="r0"} 0.2' in merged
    assert 'serving_heartbeat_age_seconds{replica="r1"} 0.5' in merged
    # HELP/TYPE appear once per family
    assert merged.count("# TYPE serving_records_total counter") == 1


# -- EngineFleet over live engines ----------------------------------------------

def test_engine_fleet_scale_replace_and_signals(ctx):
    q = InProcQueue()
    im = _im()
    fleet = EngineFleet(lambda rid: _serving(q, im=im, replica_id=rid,
                                             max_batch=8).start(),
                        q, initial=2, drain_s=2.0)
    try:
        sig = fleet.signals()
        assert sig.replicas == 2 and sig.desired == 2
        assert len(sig.heartbeat_ages) == 2
        assert sig.max_batch == 8 and sig.max_batch_ceiling == 1024
        fleet.scale_to(3)
        assert len(fleet.engines()) == 3
        fleet.retune(max_batch=16)
        # serve something so the retune lands at a batch boundary
        cin = InputQueue(q)
        cin.enqueue_tensor("x", np.ones(DIM, np.float32))
        assert OutputQueue(q).query("x", timeout_s=30) is not None
        old = {e.replica_id for e in fleet.engines()}
        victim = sorted(old)[0]
        fleet.replace(victim)
        new = {e.replica_id for e in fleet.engines()}
        assert victim not in new and len(new) == 3
        fleet.scale_to(1)
        assert len(fleet.engines()) == 1
        # external members join the signal surface
        fleet.add_external("ext-0", lambda: 42.0,
                           lambda: {"total_records": 7})
        sig = fleet.signals()
        assert sig.heartbeat_ages["ext-0"] == 42.0
        assert sig.replicas == 2 and sig.desired == 2
        assert sig.served_total >= 7
        fleet.replace("ext-0")        # replaced by an in-process engine
        assert len(fleet.engines()) == 2
        assert "ext-0" not in fleet.signals().heartbeat_ages
    finally:
        fleet.shutdown()


# -- the load-balancing front door ----------------------------------------------

def _post_json(url, doc, timeout=10):
    body = json.dumps(doc).encode()
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _tensor_record(uri):
    import base64
    arr = np.ones(DIM, np.float32)
    return {"uri": uri, "b64": base64.b64encode(arr).decode(),
            "dtype": "<f4", "shape": [DIM]}


def test_lb_front_door_routes_reroutes_and_scales(ctx):
    """One front-door port over >= 2 replica gateways: enqueue + result
    work through it, killing a replica mid-stream is never a client-visible
    failure (transport errors re-route), and a scale-up joins the rotation
    with zero client reconfig."""
    from analytics_zoo_tpu.serving.lb import LoadBalancer
    q = InProcQueue()
    im = _im()
    engines = [_serving(q, im=im, replica_id=f"lb-{i}", http_port=0).start()
               for i in range(2)]
    members = [f"http://127.0.0.1:{e._http.port}" for e in engines]
    lb = LoadBalancer(lambda: list(members), probe_interval_s=0.1).start()
    try:
        # enqueue + long-poll result through the ONE front-door port
        for i in range(6):
            code, doc, hdrs = _post_json(lb.url + "/v1/enqueue",
                                         _tensor_record(f"u{i}"))
            assert code == 200 and doc["uri"] == f"u{i}"
            assert "X-Replica-Id" in hdrs       # backend identity rides up
        for i in range(6):
            code, doc, _ = _get(lb.url + f"/v1/result/u{i}?timeout_s=20")
            assert code == 200 and "value" in doc
        # readiness reflects the member set
        code, doc, _ = _get(lb.url + "/readyz")
        assert code == 200 and len(doc["members"]) == 2
        # kill one replica HARD mid-stream: subsequent requests re-route
        # with zero 5xx-without-retry failures
        engines[0].shutdown()                   # gateway socket goes away
        for i in range(6, 14):
            code, doc, _ = _post_json(lb.url + "/v1/enqueue",
                                      _tensor_record(f"u{i}"))
            assert code == 200, (i, doc)
        for i in range(6, 14):
            code, doc, _ = _get(lb.url + f"/v1/result/u{i}?timeout_s=20")
            assert code == 200 and "value" in doc
        # scale UP during traffic: the new replica joins the rotation with
        # no client reconfig (same front-door port)
        engines.append(_serving(q, im=im, replica_id="lb-2",
                                http_port=0).start())
        members.append(f"http://127.0.0.1:{engines[-1]._http.port}")
        lb.probe_once()
        code, doc, _ = _get(lb.url + "/readyz")
        assert code == 200 and len(doc["members"]) == 2   # dead one is out
        code, doc, _ = _post_json(lb.url + "/v1/enqueue",
                                  _tensor_record("u99"))
        assert code == 200
        code, doc, _ = _get(lb.url + "/v1/result/u99?timeout_s=20")
        assert code == 200
        # front-door telemetry: every request counted, re-routes visible
        code, snap, _ = _get(lb.url + "/metrics")
        assert code == 200
        ok = [v for v in snap["lb_requests_total"]["values"]
              if v["labels"] == {"endpoint": "enqueue", "code": "200"}]
        assert ok and ok[0]["value"] == 15
        with urllib.request.urlopen(lb.url + "/metrics?format=prom",
                                    timeout=10) as r:
            prom = r.read().decode()
        assert "lb_requests_total{" in prom and "lb_members_ready" in prom
    finally:
        lb.stop()
        for e in engines:
            e.shutdown()


def test_lb_passthrough_and_no_members(ctx):
    """Semantic backend answers pass through untouched (404 not-ready, 429
    queue-full with Retry-After); an empty member set answers 503, not a
    hang."""
    from analytics_zoo_tpu.serving.lb import LoadBalancer
    q = InProcQueue(max_depth=2)
    e = _serving(q, http_port=0)       # NOT started: workers off, gateway on
    e.params.http_port = 0
    from analytics_zoo_tpu.serving.http import HealthServer
    srv = HealthServer(e, port=0).start()
    lb = LoadBalancer(lambda: [f"http://127.0.0.1:{srv.port}"],
                      probe_interval_s=0.1).start()
    try:
        code, doc, _ = _get(lb.url + "/v1/result/missing")
        assert code == 404 and doc["ready"] is False
        # fill past max_depth: the backend's 429 + Retry-After pass through
        codes = []
        for i in range(4):
            c, _, hdrs = _post_json(lb.url + "/v1/enqueue",
                                    _tensor_record(f"f{i}"))
            codes.append((c, hdrs.get("Retry-After")))
        assert (429, "1") in codes
        assert codes[0][0] == 200
    finally:
        lb.stop()
        srv.stop()
    lb2 = LoadBalancer(lambda: [], probe_interval_s=0.1).start()
    try:
        code, doc, _ = _get(lb2.url + "/readyz")
        assert code == 503
        code, doc, _ = _post_json(lb2.url + "/v1/enqueue",
                                  _tensor_record("x"))
        assert code == 503 and "no replica gateway" in doc["error"]
    finally:
        lb2.stop()


# -- per-leaf buffer donation ---------------------------------------------------

def test_donation_safe_jit_silences_warning_keeps_numerics_and_donation():
    """The probe catches XLA's 'donated buffers were not usable' warning,
    re-jits donating only usable leaves (warning gone for good), keeps
    numerics identical, and KEEPS donating leaves that are usable."""
    import warnings

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.utils.donation import donation_safe_jit

    def step(params, x):
        # 'w' has a matching output (usable donation); 'tab' is consumed
        # into a scalar only (never usable)
        y = params["w"] * 2.0 + x
        s = jnp.take(params["tab"], jnp.array([0, 1])).sum()
        return {"w": y, "tab_sum": s + y.sum()}

    def fresh():
        return {"w": jnp.arange(8, dtype=jnp.float32),
                "tab": jnp.arange(16, dtype=jnp.float32)}

    x = jnp.ones(8, jnp.float32)
    ref = jax.jit(step)(fresh(), x)
    safe = donation_safe_jit(step, donate_argnums=(0,))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        outs = [safe(fresh(), x) for _ in range(3)]
    assert not [w for w in caught
                if "donated buffers" in str(w.message)], caught
    for out in outs:
        assert np.allclose(out["w"], ref["w"])
        assert float(out["tab_sum"]) == float(ref["tab_sum"])
    # the usable leaf IS still donated (its input buffer was consumed),
    # the unusable one is NOT (still readable)
    p = fresh()
    safe(p, x)
    assert p["w"].is_deleted()
    assert not p["tab"].is_deleted()
    assert float(p["tab"][3]) == 3.0


# -- chaos acceptance A/B (slow): 10x swing + replica SIGKILL -------------------

@pytest.mark.slow
@pytest.mark.timeout(280)
def test_chaos_swing_ab_autoscale_on_holds_slo(tmp_path, ctx):
    """The PR 10 acceptance scenario, asserted structurally: under a 10x
    offered-load swing plus one replica SIGKILL mid-swing (a REAL
    subprocess over the shared FileQueue spool), autoscale-on holds the
    stated e2e p99 SLO, loses zero records, replaces the dead replica and
    scales the fleet; autoscale-off at the initial fleet size violates the
    SLO by a wide margin."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serving_bench

    slo_ms = 5000.0
    common = ["--load-profile", "swing", "--chaos", "sigkill",
              "--phase-s", "4", "--slo-ms", str(slo_ms),
              "--drain-timeout-s", "60"]
    on = serving_bench.main(
        common + ["--autoscale", "on",
                  "--json", str(tmp_path / "on.json")])
    off = serving_bench.main(
        common + ["--autoscale", "off",
                  "--json", str(tmp_path / "off.json")])
    # ON: every record resolved, none lost through the SIGKILL
    assert on["served"] + on["shed"] == on["enqueued"]
    assert on["shed"] <= 0.02 * on["enqueued"]
    # ON: holds the stated SLO
    assert on["client_p99_ms"] is not None
    assert on["client_p99_ms"] <= slo_ms, on
    assert on["slo_violated"] is False
    # ON: the controller actually closed the loop — replaced the SIGKILLed
    # replica and scaled the fleet; replica count recovered
    assert on["decision_counts"]["replace_replica"] >= 1
    assert on["decision_counts"]["scale_up"] >= 1
    assert on["final_alive"] >= on["initial_replicas"]
    assert on["max_replicas_seen"] > on["initial_replicas"]
    # OFF at the initial fleet size: violates the SLO (or sheds hugely)
    assert off["slo_violated"] is True
    assert (off["client_p99_ms"] is None
            or off["client_p99_ms"] > slo_ms
            or off["shed"] > 10 * max(on["shed"], 1))
    # and the A/B separation is wide, not marginal
    if off["client_p99_ms"] and on["client_p99_ms"]:
        assert on["client_p99_ms"] < 0.7 * off["client_p99_ms"]
