"""CPU rehearsal of the per-layer metrics under ``setup_s`` (PR 37): the reader
``window_start`` on facts whose answer is known, on facts shaped like the parent
commit's (no ``startup_*`` key: nothing is read, nothing raises), and on one toy run
of the real command, where the six times must add up to the run's own ``setup_s``.

    python -m pytest benchmark/tests -q
"""

import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TIMES = ["setup.import_s", "setup.weights_s", "setup.engine_init_s",
         "setup.warm_lower_s", "setup.warm_compile_s", "setup.warm_traffic_s"]
NEW = TIMES + ["setup.cache_miss_programs", "setup.code_mb"]
# what the six times leave of a toy run's set-up: the warm-up loop between two
# programs (the arguments a program is lowered from, its memory_analysis, the
# progress callback).  Tenths of a second of 10-20 on this machine's CPU; the
# limit is a twentieth so that a loaded machine does not fail the rehearsal
REST = 0.05


def _facts():
    """A start at monotonic 100 s: imported at 104, engine at 110, warm-up from 111
    to 140 (lower 12 s + compile 16 s + 1 s of loop), window at 150."""
    start = {"startup_t.imported": 104.0,
             "startup_t.model_loaded": 109.9, "startup_t.engine": 110.0,
             "startup_t.warm_begin": 111.0, "startup_t.ready": 140.0,
             "startup_s.lower": 12.0, "startup_s.compile": 16.0,
             "startup_n.programs": 5, "startup_n.cache_misses": 2,
             "startup_b.code": 3 * 2 ** 20, "decode_steps": 7}
    end = dict(start, **{"startup_s.lower": 13.0, "startup_n.cache_misses": 3,
                         "decode_steps": 70})
    return {"window": [150.0, 195.0], "setup_seconds": 50.0,
            "counters": {"window": [start, end]}}


def test_the_reader_on_facts_whose_answer_is_known():
    import run
    facts = _facts()
    got = {name: run.read_metric(name, facts) for name in NEW}
    assert got == pytest.approx({
        "setup.import_s": 4.0, "setup.weights_s": 6.0, "setup.engine_init_s": 1.0,
        "setup.warm_lower_s": 12.0, "setup.warm_compile_s": 16.0,
        "setup.warm_traffic_s": 10.0, "setup.cache_miss_programs": 2.0,
        "setup.code_mb": 3.0})
    # the START snapshot: what a late compile adds inside the window is not set-up
    assert sum(got[name] for name in TIMES) == pytest.approx(50.0 - 1.0)
    from readers import window_start
    assert window_start.read(facts, since="process", until="window") \
        == pytest.approx(50.0)
    assert window_start.read(facts, key="decode_steps", scale=2.0) == 14.0


@pytest.mark.parametrize("facts", [
    {k: v for k, v in _facts().items() if k != "counters"},        # a training run
    dict(_facts(), counters={"window": [None, None]}),
    dict(_facts(), counters={"window": [
        {k: v for k, v in snap.items() if not k.startswith("startup_")}
        for snap in _facts()["counters"]["window"]]}),              # the parent
    dict(_facts(), counters={"trace": _facts()["counters"]["window"]}),
    {"counters": _facts()["counters"]},                   # no window, no set-up time
], ids=["no-counters", "empty-pair", "parent", "no-window-pair", "no-window"])
def test_facts_without_the_record_read_nothing_and_raise_nothing(facts):
    import run
    got = {name: run.read_metric(name, facts) for name in NEW}
    if "window" not in facts:
        # the counters alone still answer the metrics that read one key
        assert got["setup.warm_lower_s"] == 12.0
        assert got["setup.import_s"] is None
        assert got["setup.warm_traffic_s"] is None
    else:
        assert got == dict.fromkeys(NEW)


def test_the_eight_entries_are_in_the_manifest_under_setup_s():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = [w["name"] for w in manifest["workloads"]]
    listed = [m["name"] for m in manifest["per_layer"]]
    at = listed.index(NEW[0])
    assert listed[at:at + len(NEW)] == NEW            # together, the issue's order
    for m in manifest["per_layer"][at:at + len(NEW)]:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] != "device_trace"          # they ride every plain run
        assert m["workloads"] == cells[:4]            # one entry, all four cells
        spec = json.load(open(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json")))
        assert spec["reader"] == "window_start"


def test_a_toy_run_cuts_its_own_setup_s(tmp_path):
    from test_harness import _toy
    line = _toy(tmp_path, "toy-lm.batchy", 0)
    assert line["correct"] is True
    cut = line["notes"]["per_layer_untraced"]
    assert set(NEW) <= set(cut)
    assert all(cut[name] is not None and cut[name] >= 0 for name in NEW), cut
    setup = line["metrics"]["setup_s"]["value"]
    rest = setup - sum(cut[name] for name in TIMES)
    assert 0 <= rest <= REST * setup, (setup, cut)
    # the toy's warmed set: one decode program, prefill batches 1, 2, 4 x 2 buckets
    assert cut["setup.warm_lower_s"] > 0 and cut["setup.warm_compile_s"] > 0
    assert 0 <= cut["setup.cache_miss_programs"] <= 7
