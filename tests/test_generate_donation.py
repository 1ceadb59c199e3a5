"""The paged decode program takes the KV pool over in place (PR 29).

``pdecode`` donates the pool pytree (``pprefill`` and ``pshared`` do not:
their donated executables do not fit the TPU's compile cache, ``PERF.md``
section 6).  What that has to guarantee, checked here on the CPU with a tiny
``TransformerLM``:

- the compiled decode program aliases every pool leaf, the prefill programs
  none, and compiling warns of no unusable donation;
- a decode call consumes the pool it was given and ``lane.state`` is its
  live successor; ``stats()`` counts the bytes passed and the bytes aliased;
- the served tokens are those of the same programs without donation;
- the warm-up thread lowers from shapes, so it can run beside a decoding
  generate thread;
- a call that fails before execution leaves the pool alone (singleton
  retry as before); one that fails after the runtime took the buffers ends
  the lane's requests, rebuilds the pool, and the lane serves on.
"""

import threading
import warnings

import numpy as np
import pytest

pytestmark = pytest.mark.kvcache

PAGED_KINDS = ("pprefill", "pshared", "pdecode")
DONATING = ("pdecode",)
POOL_ARG = {"pprefill": 3, "pshared": 5, "pdecode": 1}


# -- helpers ------------------------------------------------------------------

def _im():
    import jax
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    lm = TransformerLM(vocab_size=64, hidden=32, n_head=2, n_layers=2,
                       max_len=64)
    return InferenceModel().do_load_model(
        lm, lm.build(jax.random.PRNGKey(0)), {})


def _batcher(kv_quant="off", prefix_cache=False, **kw):
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams)
    gen = dict(paged=True, block_len=4, max_active_slots=4,
               max_prompt_len=16, max_tokens=6, bucket_lens=[32],
               prefill_buckets=[8, 16], decode_quantum=2,
               kv_quant=kv_quant, prefix_cache=prefix_cache)
    gen.update(kw)
    return ContinuousBatcher(_im(), GenerationParams(**gen))


def _undonated(b):
    """The same batcher as before the change: the three paged functions
    jitted again, none with ``donate_argnums``."""
    import jax
    b._programs[("pfns",)] = tuple(
        jax.jit(fn.__wrapped__) for fn in b._paged_fns())
    return b


def _reqs(n=7, shared=False):
    """Prompts of 2-16 tokens in both prefill buckets; with ``shared``
    every other one starts with the same 8-token system prefix."""
    g = np.random.default_rng(11)
    system = g.integers(1, 64, 8).astype(np.int32)
    out = []
    for i in range(n):
        if shared and i % 2 == 0:
            tail = g.integers(1, 64, int(g.integers(1, 9))).astype(np.int32)
            prompt = np.concatenate([system, tail])
        else:
            prompt = g.integers(1, 64,
                                int(g.integers(2, 17))).astype(np.int32)
        out.append((f"r{i}", prompt, 3 + i % 4))
    return out


def _submit(b, reqs):
    from analytics_zoo_tpu.serving.generate import GenRequest
    for rid, prompt, budget in reqs:
        assert b.submit(GenRequest(rid, prompt, max_tokens=budget))


def _run(b, n_expected, first=(), max_steps=2000):
    """Step until ``n_expected`` requests reached a terminal event (``first``:
    the events of a step already taken); returns ({rid: tokens} of the
    finished, {rid: error} of the quarantined)."""
    done, bad = {}, {}
    for i in range(max_steps):
        for ev in (first if i == 0 else b.step()):
            if ev.kind == "finish":
                done[ev.rid] = list(ev.tokens)
            elif ev.kind == "quarantine":
                bad[ev.rid] = ev.error
            assert ev.kind != "shed"
        if len(done) + len(bad) >= n_expected:
            return done, bad
    raise AssertionError(f"stalled: {len(done)} + {len(bad)} of "
                         f"{n_expected}")


def _leaves(b):
    import jax
    return jax.tree.leaves(b._lanes[0].state)


def _fail_after_taking_pool(kind):
    """A paged executable as a call that fails on the device leaves it: the
    donated pool's buffers are gone, then the error surfaces."""
    import jax

    def exe(*args):
        for leaf in jax.tree.leaves(args[POOL_ARG[kind]]):
            leaf.delete()
        raise RuntimeError("injected: failed after donation")
    return exe


def _fail_before_execution(*args):
    raise ValueError("injected: failed before execution")


# -- every program aliases every pool leaf ------------------------------------

@pytest.fixture(scope="module", params=["off", "int8"])
def warmed(request):
    """One batcher a ``kv_quant``, prefix cache on (so ``pshared`` programs
    exist), its whole program set compiled with warnings recorded."""
    b = _batcher(kv_quant=request.param, prefix_cache=True)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        doc = b.warm()
    assert doc["failed"] == 0, doc["errors"]
    return b, [str(w.message) for w in caught]


@pytest.mark.parametrize("kind", PAGED_KINDS)
def test_compiled_decode_aliases_every_pool_leaf_prefill_none(warmed, kind):
    b, messages = warmed
    lane = b._lanes[0]
    assert not [m for m in messages if "donated buffers" in m]
    exes = {k: v for k, v in b._programs.items() if k[0] == kind}
    assert exes, f"warm-up compiled no {kind} program"
    n_leaves = len(_leaves(b))
    assert n_leaves == (12 if b.gen.kv_quant == "int8" else 4)
    for key, exe in exes.items():
        aliased = exe.memory_analysis().alias_size_in_bytes
        want = lane.state_nbytes if kind in DONATING else 0
        assert aliased == want, \
            f"{key}: {aliased} of {lane.state_nbytes} pool bytes aliased"
        assert b._record_of[key]["alias_bytes"] == aliased


# -- a call consumes the pool, and the counters say so ------------------------

@pytest.mark.parametrize("kv_quant", ["off", "int8"])
def test_step_takes_the_pool_over_in_place(kv_quant):
    b = _batcher(kv_quant=kv_quant)
    before = _leaves(b)
    _submit(b, _reqs(2))
    b.step()                      # prefill call(s), then one decode call
    mid = _leaves(b)
    assert not any(leaf.is_deleted() for leaf in mid)
    b.step()                      # a decode call alone
    assert all(leaf.is_deleted() for leaf in mid)
    after = _leaves(b)
    assert not any(leaf.is_deleted() for leaf in after)
    assert [(x.shape, x.dtype) for x in after] \
        == [(x.shape, x.dtype) for x in before]
    s = b.stats()
    calls = b.program_stats()["programs"]
    nbytes = b._lanes[0].state_nbytes
    assert s["state_bytes_passed"] == sum(calls.values()) * nbytes > 0
    assert s["state_bytes_aliased"] == calls["paged_decode@32"] * nbytes \
        == 2 * nbytes
    assert s["pool_rebuilds"] == 0
    # the ledger's readers go on working on whatever the pool is now
    assert b.state_bytes() >= b._lanes[0].state_nbytes


@pytest.mark.parametrize("kv_quant,prefix_cache", [
    ("off", False), ("int8", False), ("off", True), ("int8", True)])
def test_tokens_equal_the_undonated_programs(kv_quant, prefix_cache):
    """Several admissions and boundaries (7 requests over 4 slots): the same
    tokens as the same three functions compiled without donation, whose
    calls alias nothing."""
    reqs = _reqs(7, shared=prefix_cache)
    plain = _undonated(_batcher(kv_quant, prefix_cache))
    _submit(plain, reqs)
    want, bad = _run(plain, len(reqs))
    assert not bad
    assert plain.stats()["state_bytes_aliased"] == 0 \
        < plain.stats()["state_bytes_passed"]

    b = _batcher(kv_quant, prefix_cache)
    _submit(b, reqs)
    got, bad = _run(b, len(reqs))
    assert not bad and got == want
    s = b.stats()
    decodes = b.program_stats()["programs"]["paged_decode@32"]
    assert s["state_bytes_aliased"] == decodes * b._lanes[0].state_nbytes \
        < s["state_bytes_passed"]
    if prefix_cache:
        assert s["pool"]["prefix_hits"] > 0
        assert any(k.startswith("paged_shared")
                   for k in b.program_stats()["programs"])


# -- the warm-up thread never holds the live pool -----------------------------

def test_warm_on_a_second_thread_while_requests_decode():
    solo = _batcher(prefix_cache=True)
    assert solo.warm()["failed"] == 0
    reqs = _reqs(7, shared=True)
    _submit(solo, reqs)
    want, _ = _run(solo, len(reqs))

    b = _batcher(prefix_cache=True)
    _submit(b, reqs)
    first = b.step()               # requests are decoding
    out = {}
    t = threading.Thread(target=lambda: out.update(b.warm()))
    t.start()
    got, bad = _run(b, len(reqs), first)
    while t.is_alive():            # keep the pool changing hands under it
        _submit(b, [("x", reqs[0][1], 2)])
        _run(b, 1)
    t.join()
    assert out["failed"] == 0 and out["errors"] == []
    assert not bad and got == want
    assert set(b._programs) == set(solo._programs)
    assert b.stats()["pool_rebuilds"] == 0


# -- a failed call ------------------------------------------------------------

def test_prefill_failing_before_execution_retries_the_members_alone():
    reqs = [("a", np.arange(1, 6, dtype=np.int32), 3),
            ("b", np.arange(2, 8, dtype=np.int32), 3)]
    ref = _batcher()
    _submit(ref, reqs)
    want, _ = _run(ref, 2)

    b = _batcher()
    b._programs[("pprefill", 2, 8)] = _fail_before_execution
    before = _leaves(b)
    _submit(b, reqs)
    got, bad = _run(b, 2)
    assert not bad and got == want          # served by two b=1 programs
    assert "paged_prefill:b1xp8" in b.program_stats()["programs"]
    assert b.stats()["pool_rebuilds"] == 0
    assert not any(leaf.is_deleted() for leaf in _leaves(b))


def test_singleton_prefill_failing_before_execution_quarantines_it():
    b = _batcher()
    b._programs[("pprefill", 1, 8)] = _fail_before_execution
    before = _leaves(b)
    _submit(b, [("a", np.arange(1, 6, dtype=np.int32), 3)])
    done, bad = _run(b, 1)
    assert not done and "failed before execution" in bad["a"]
    assert b.stats()["pool_rebuilds"] == 0
    assert not any(leaf.is_deleted() for leaf in before)
    assert b._pool.used_blocks == 0 and b.active == 0


def test_decode_failing_before_execution_propagates_with_the_pool_alive():
    b = _batcher()
    _submit(b, [("a", np.arange(1, 6, dtype=np.int32), 6)])
    b.step()
    real = b._programs[("pdecode", 32)]
    b._programs[("pdecode", 32)] = _fail_before_execution
    with pytest.raises(ValueError, match="before execution"):
        b.step()
    assert b.stats()["pool_rebuilds"] == 0 and b.active == 1
    assert not any(leaf.is_deleted() for leaf in _leaves(b))
    b._programs[("pdecode", 32)] = real
    done, bad = _run(b, 1)
    assert len(done) == 1 and not bad


@pytest.mark.parametrize("kind,key", [
    ("pdecode", ("pdecode", 32)),
    ("pprefill", ("pprefill", 1, 16)),
    ("pshared", ("pshared", 1, 8, 2)),
])
def test_call_failing_after_donation_ends_the_lane_and_rebuilds(
        kind, key, caplog):
    """``old`` (prompt in bucket 8, first half of ``later``'s) is decoding
    when the call under test takes the pool and fails: ``old`` and the
    failing admission's members end with quarantine events, a group
    reserved behind them goes back to the waiting room, and the fresh pool
    serves what comes next as a new batcher would."""
    system = np.arange(1, 9, dtype=np.int32)
    victim = {"pdecode": None,
              "pprefill": ("v", np.arange(3, 15, dtype=np.int32), 4),
              "pshared": ("v", np.concatenate(
                  [system, np.arange(20, 25, dtype=np.int32)]), 4)}[kind]
    # the admission group behind the victim's: a miss in bucket 8 behind a
    # miss in bucket 16, a hit with a 16-token suffix behind one with 8
    later = ("l", np.concatenate([system, np.arange(30, 39, dtype=np.int32)])
             if kind == "pshared" else np.arange(5, 9, dtype=np.int32), 4)
    after = ("n", np.arange(7, 18, dtype=np.int32), 5)
    ref = _batcher(prefix_cache=True, max_prompt_len=20)
    _submit(ref, [later, after])
    want, _ = _run(ref, 2)

    b = _batcher(prefix_cache=True, max_prompt_len=20)
    _submit(b, [("old", np.concatenate([system, [9]]).astype(np.int32), 6)])
    b.step()
    assert b.active == 1 and b.stats()["pool"]["prefix_entries"] == 1
    real = b._programs.get(key)
    b._programs[key] = _fail_after_taking_pool(kind)
    if victim is not None:
        _submit(b, [victim, later])
    with caplog.at_level("ERROR", logger="analytics_zoo_tpu.serving.generate"):
        events = b.step()
    lost = {ev.rid: ev.error for ev in events if ev.kind == "quarantine"}
    assert set(lost) == ({"old"} if victim is None else {"old", "v"})
    assert all("failed after donation" in e for e in lost.values())
    assert "KV pool lost" in lost["old"]
    s = b.stats()
    assert s["pool_rebuilds"] == 1 and s["active_slots"] == 0
    assert s["pool"]["used_blocks"] == 0
    assert s["pool"]["prefix_entries"] == 0
    assert len([r for r in caplog.records if r.levelname == "ERROR"
                and r.name.endswith("serving.generate")]) == 1
    leaves = _leaves(b)
    assert not any(leaf.is_deleted() for leaf in leaves)
    assert not any(np.asarray(leaf).any() for leaf in leaves)
    if victim is not None:
        assert b.waiting == 1          # `later`: reserved, then sent back
    else:
        _submit(b, [later])
    if real is None:
        del b._programs[key]
    else:
        b._programs[key] = real
    _submit(b, [after])
    got, bad = _run(b, 2)
    assert not bad and got == want


def test_singleton_retry_that_loses_the_pool_requeues_the_rest():
    """A prefix-hit group of two fails before execution (pool alive), so
    its members are retried alone; the first retry then takes the pool
    with it.  The second member reserved shared prefix pages that are
    zeros now: it must not run on them, it goes back to the waiting room
    and is served from the fresh pool as a new batcher would serve it."""
    system = np.arange(1, 9, dtype=np.int32)
    h1 = ("h1", np.concatenate([system, np.arange(20, 25, dtype=np.int32)]), 4)
    h2 = ("h2", np.concatenate([system, np.arange(30, 34, dtype=np.int32)]), 4)
    ref = _batcher(prefix_cache=True, max_prompt_len=20)
    _submit(ref, [h2])
    want, _ = _run(ref, 1)

    b = _batcher(prefix_cache=True, max_prompt_len=20)
    _submit(b, [("old", np.concatenate([system, [9]]).astype(np.int32), 6)])
    b.step()
    assert b.stats()["pool"]["prefix_entries"] == 1
    calls = []
    take = _fail_after_taking_pool("pshared")

    def single(*args):
        calls.append(args)
        take(*args)
    b._programs[("pshared", 2, 8, 2)] = _fail_before_execution
    b._programs[("pshared", 1, 8, 2)] = single
    _submit(b, [h1, h2])
    events = b.step()
    lost = {ev.rid: ev.error for ev in events if ev.kind == "quarantine"}
    assert set(lost) == {"old", "h1"} and len(calls) == 1
    s = b.stats()
    assert s["pool_rebuilds"] == 1 and s["active_slots"] == 0
    assert s["pool"]["used_blocks"] == 0 and b.waiting == 1
    del b._programs[("pshared", 2, 8, 2)], b._programs[("pshared", 1, 8, 2)]
    got, bad = _run(b, 1)
    assert not bad and got == want
