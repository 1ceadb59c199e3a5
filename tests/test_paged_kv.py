"""Paged KV block pool, prefix sharing and int8 KV lanes (PR 18).

Covers the four layers the tentpole touched:

- ``serving/kvpool.py`` — block pool refcounting + the LRU prefix index
  (pure host structures, no device work).
- ``inference/quantize.py`` — the int8 KV pack/unpack contract (scale
  formula golden + the quantize -> append -> dequantize roundtrip).
- ``ops/paged_attention.py`` — kernel (interpret) vs XLA-oracle parity,
  float and int8, aligned and ragged block counts, plus the structural
  claim that the XLA path is bitwise-exact vs a monolithic cache.
- ``serving/generate.py`` — end-to-end scheduler parity (float paged
  tokens EXACTLY match monolithic), prefix-cache hits on a shared-prompt
  mix, pool-exhaustion shedding + the typed flight-recorder event, the
  ``state_bytes`` ledger golden (the PR 18 aux bugfix), zero steady-state
  compiles after warm-up, the paged warm-up manifest, and the fleet
  ``kv_pool`` aggregation.
- the seam between the two (PR 30): a model with another state layout
  behind the paged contract is served by the scheduler as it stands, and
  ``TransformerLM``'s five forward paths agree bitwise.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.kvcache


# -- helpers ------------------------------------------------------------------

def _im(vocab=64, hidden=32, n_head=2, n_layers=1, max_len=64):
    import jax
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    lm = TransformerLM(vocab_size=vocab, hidden=hidden, n_head=n_head,
                       n_layers=n_layers, max_len=max_len)
    params = lm.build(jax.random.PRNGKey(0))
    return InferenceModel().do_load_model(lm, params, {}), lm


def _batcher(im, **kw):
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams)
    return ContinuousBatcher(im, GenerationParams(**kw))


def _drive(batcher, reqs, tag=""):
    """Submit every (rid, prompt, budget) and step to completion; returns
    {rid: tokens}."""
    from analytics_zoo_tpu.serving.generate import GenRequest
    for rid, prompt, budget in reqs:
        assert batcher.submit(GenRequest(tag + rid, prompt,
                                         max_tokens=budget))
    done = {}
    for _ in range(10_000):
        for ev in batcher.step():
            if ev.kind == "finish":
                done[ev.rid] = list(ev.tokens)
            assert ev.kind not in ("shed", "quarantine"), \
                f"{ev.kind} on {ev.rid}: {ev.error}"
        if len(done) == len(reqs):
            return {rid: done[tag + rid] for rid, _, _ in reqs}
    raise AssertionError(f"stalled: {len(done)}/{len(reqs)} finished")


def _shared_reqs(n=8, sys_len=16, pmax=24, vocab=64, budgets=(2, 3, 5)):
    """Half the prompts share a sys_len-token system prefix."""
    g = np.random.default_rng(3)
    system = g.integers(1, vocab, sys_len).astype(np.int32)
    reqs = []
    for i in range(n):
        if i % 2 == 0:
            tail = g.integers(1, vocab, int(g.integers(1, pmax - sys_len
                                                       + 1)))
            prompt = np.concatenate([system, tail.astype(np.int32)])
        else:
            prompt = g.integers(1, vocab,
                                int(g.integers(2, pmax + 1))).astype(np.int32)
        reqs.append((f"r{i}", prompt, budgets[i % len(budgets)]))
    return reqs


# -- block pool ---------------------------------------------------------------

def test_block_pool_alloc_release_refcount():
    from analytics_zoo_tpu.serving.kvpool import TRASH_BLOCK, BlockPool
    pool = BlockPool(8, 16)
    assert pool.n_blocks == 8 and pool.free_blocks == 8
    a = pool.alloc(3)
    assert a is not None and len(a) == 3
    assert TRASH_BLOCK not in a, "block 0 is reserved for garbage writes"
    assert pool.free_blocks == 5 and pool.used_blocks == 3
    # sharing: addref bumps, release decrements, the block only returns
    # to the free list at refcount zero
    pool.addref([a[0]])
    assert pool.refcount(a[0]) == 2
    assert pool.release([a[0]]) == 0
    assert pool.refcount(a[0]) == 1 and pool.free_blocks == 5
    assert pool.release(a) == 3
    assert pool.free_blocks == 8 and pool.used_blocks == 0


def test_block_pool_all_or_nothing():
    from analytics_zoo_tpu.serving.kvpool import BlockPool
    pool = BlockPool(4, 16)
    assert pool.alloc(4) is not None
    before = pool.free_blocks
    assert pool.alloc(1) is None, "over-allocation must fail"
    assert pool.free_blocks == before, "failed alloc must not leak"


def test_prefix_index_lookup_register_evict():
    from analytics_zoo_tpu.serving.kvpool import BlockPool, PrefixIndex
    pool = BlockPool(16, 4)
    idx = PrefixIndex(pool)
    toks = np.arange(12, dtype=np.int32)
    blocks = pool.alloc(3)
    assert idx.register(toks, blocks)
    held = pool.refcount(blocks[0])
    # longest-prefix hit, capped by max_blocks; the hit addrefs for the
    # caller on top of the cache's own hold
    k, ids = idx.lookup(np.concatenate([toks, [99]]), max_blocks=3)
    assert k == 3 and ids == blocks
    assert pool.refcount(blocks[0]) == held + 1
    pool.release(ids)
    # entries hit at their exact registered boundary only: a shorter
    # query misses the 3-block entry until its own 2-block prefix is
    # registered
    assert idx.lookup(toks[:8], max_blocks=2) == (0, [])
    assert idx.register(toks[:8], blocks[:2])
    k2, ids2 = idx.lookup(toks[:10], max_blocks=2)
    assert k2 == 2 and ids2 == blocks[:2]
    pool.release(ids2)
    # a miss leaves nothing held
    k3, ids3 = idx.lookup(np.array([7, 7, 7, 7], np.int32), max_blocks=1)
    assert k3 == 0 and ids3 == []
    s = idx.stats()
    assert s["hits"] == 2 and s["misses"] == 2
    # eviction drops the cache holds; with the slot's own alloc hold
    # released first, the pool gets every block back.  (evict_for is
    # demand-driven — it only evicts while the pool is short.)
    pool.release(blocks)
    assert pool.free_blocks == pool.n_blocks - 3, \
        "cache holds must keep registered blocks resident"
    idx.evict_for(pool.n_blocks)
    assert len(idx) == 0
    assert pool.free_blocks == pool.n_blocks


# -- int8 KV pack/unpack ------------------------------------------------------

def test_kv_pack_int8_roundtrip_golden():
    from analytics_zoo_tpu.inference.quantize import (kv_pack_int8,
                                                      kv_unpack_int8)
    g = np.random.default_rng(0)
    x = np.asarray(g.normal(size=(5, 16, 2, 8)) * 3.0, np.float32)
    q, scale = kv_pack_int8(x)
    q, scale = np.asarray(q), np.asarray(scale)
    assert q.dtype == np.int8 and scale.shape == (5, 2)
    # scale golden: symmetric absmax over (token, head_dim) per
    # (block, head)
    amax = np.abs(x).max(axis=(-3, -1))
    np.testing.assert_allclose(scale, np.maximum(amax, 1e-12) / 127.0,
                               rtol=1e-6)
    # roundtrip error bound: half a quantization step everywhere
    y = np.asarray(kv_unpack_int8(q, scale))
    err = np.abs(y - x)
    bound = scale[:, None, :, None] * 0.5 + 1e-7
    assert (err <= bound).all(), \
        f"roundtrip error {err.max()} above half-step bound"
    # all-zero blocks must not divide by zero and decode to zero
    q0, s0 = kv_pack_int8(np.zeros((1, 4, 2, 8), np.float32))
    assert np.asarray(kv_unpack_int8(q0, s0)).max() == 0.0


def test_kv_quantize_append_dequant_roundtrip():
    """The decode append contract: the staging buffer re-quantizes the
    WHOLE partial block from exact f32 each step, so the resident block
    always equals pack(exact block) — appending never compounds error."""
    from analytics_zoo_tpu.inference.quantize import (kv_pack_int8,
                                                      kv_unpack_int8)
    g = np.random.default_rng(1)
    bl, nh, hd = 8, 2, 4
    stage = np.zeros((1, bl, nh, hd), np.float32)
    for t in range(bl):
        stage[0, t] = g.normal(size=(nh, hd))
        q, s = kv_pack_int8(stage)
        y = np.asarray(kv_unpack_int8(q, s))
        ref_q, ref_s = kv_pack_int8(stage.copy())
        np.testing.assert_array_equal(np.asarray(q), np.asarray(ref_q))
        bound = np.asarray(s)[:, None, :, None] * 0.5 + 1e-7
        assert (np.abs(y - stage) <= bound).all(), f"step {t} drifted"


# -- paged attention kernel ---------------------------------------------------

def _pool_case(seed, A, n_table, bl, nh, hd, lengths, trash_rows=()):
    """Random monolithic caches scattered into a pool under a permuted
    block order, plus garbage in the unreferenced blocks.  ``trash_rows``
    are inactive slots: every table entry is block 0, the trash block."""
    g = np.random.default_rng(seed)
    C = n_table * bl
    q = np.asarray(g.normal(size=(A, nh, hd)), np.float32)
    kc = np.asarray(g.normal(size=(A, C, nh, hd)), np.float32)
    vc = np.asarray(g.normal(size=(A, C, nh, hd)), np.float32)
    n_blocks = 1 + A * n_table
    perm = g.permutation(np.arange(1, n_blocks))
    tables = perm.reshape(A, n_table).astype(np.int32)
    kp = np.asarray(g.normal(size=(n_blocks, bl, nh, hd)), np.float32)
    vp = np.asarray(g.normal(size=(n_blocks, bl, nh, hd)), np.float32)
    for a in range(A):
        for t in range(n_table):
            kp[tables[a, t]] = kc[a, t * bl:(t + 1) * bl]
            vp[tables[a, t]] = vc[a, t * bl:(t + 1) * bl]
    tables[list(trash_rows)] = 0
    return q, kc, vc, kp, vp, tables, np.asarray(lengths, np.int32)


def _fold(pool):
    """(n_blocks, bl, nh, hd) -> the pool layout (n_blocks, bl, nh*hd)."""
    pool = np.asarray(pool)
    return pool.reshape(pool.shape[:2] + (-1,))


def _ref_attention(q, kc, vc, lengths):
    hd = q.shape[-1]
    s = np.einsum("ahd,athd->aht", q, kc) / np.sqrt(hd)
    mask = np.arange(kc.shape[1])[None, None, :] < lengths[:, None, None]
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("aht,athd->ahd", p, vc)


@pytest.mark.parametrize("lengths", [(32, 32, 32, 32),    # block-aligned
                                     (32, 17, 9, 1)])     # ragged
def test_paged_attention_xla_matches_reference(lengths):
    from analytics_zoo_tpu.ops.paged_attention import paged_attention_xla
    q, kc, vc, kp, vp, tables, lens = _pool_case(0, 4, 4, 8, 2, 8, lengths)
    out = np.asarray(paged_attention_xla(q, _fold(kp), _fold(vp), tables,
                                         lens))
    np.testing.assert_allclose(out, _ref_attention(q, kc, vc, lens),
                               rtol=2e-5, atol=2e-5)


# (rows, n_table, block_len, heads, head_dim, lengths, trash rows).  The
# kernel folds G table entries a grid step, G * block_len = 128 positions
# (block_len 8 -> G 16), a narrower table being one group.
KERNEL_CASES = [
    pytest.param(4, 4, 8, 2, 8, (32, 32, 32, 32), (), id="aligned"),
    pytest.param(4, 4, 8, 2, 8, (32, 17, 9, 1), (), id="ragged"),
    # three groups: lengths inside the first, on its boundary, one past
    # it, at 1, at the full table, and on the second boundary
    pytest.param(6, 48, 8, 2, 8, (100, 128, 129, 1, 384, 256), (),
                 id="three-groups"),
    # n_table 20 is not a multiple of G 16: the second group is padded
    pytest.param(4, 20, 8, 2, 8, (160, 129, 128, 5), (),
                 id="table-not-a-multiple"),
    # inactive slots between live rows: all-trash tables, length 1
    pytest.param(5, 32, 8, 2, 8, (1, 200, 1, 256, 1), (0, 2, 4),
                 id="trash-rows"),
    # as served: an idle slot's cursor climbs by the quantum to the lane,
    # so trash rows sit at the full lane and at mid lengths
    pytest.param(5, 32, 8, 2, 8, (256, 200, 256, 129, 256), (0, 2, 4),
                 id="trash-rows-full-lane"),
    pytest.param(6, 32, 8, 2, 8, (77, 1, 130, 256, 200, 129), (0, 2, 5),
                 id="trash-rows-mid-lengths"),
    # one live row among seven trash rows: the cross-row prefetch chain
    # skips them (first, last, middle)
    pytest.param(8, 32, 8, 2, 8, (200, 256, 1, 130, 77, 256, 17, 129),
                 (1, 2, 3, 4, 5, 6, 7), id="one-live-first"),
    pytest.param(8, 32, 8, 2, 8, (256, 1, 130, 77, 256, 17, 129, 200),
                 (0, 1, 2, 3, 4, 5, 6), id="one-live-last"),
    pytest.param(8, 32, 8, 2, 8, (256, 1, 130, 200, 256, 17, 129, 77),
                 (0, 1, 2, 4, 5, 6, 7), id="one-live-middle"),
    # no live row at all: nothing fetched, every output row zero
    pytest.param(4, 32, 8, 2, 8, (1, 256, 100, 17), (0, 1, 2, 3),
                 id="all-trash"),
    # block_len 16 -> G 8, n_table 12: two groups, the second half empty
    pytest.param(3, 12, 16, 3, 8, (192, 130, 16), (), id="block-len-16"),
    # the benchmark cells' shape (gpt2-large, 8 slots, lane 1024)
    pytest.param(8, 64, 16, 20, 64, (1, 16, 17, 128, 129, 600, 1023, 1024),
                 (), id="cell-shape"),
]


def _check_trash_rows(kern, oracle, trash):
    """A trash row (an inactive slot) reads exactly zero on both paths,
    whatever its length, and nothing is 0/0."""
    assert np.isfinite(kern).all()
    assert not kern[list(trash)].any() and not oracle[list(trash)].any()


@pytest.mark.parametrize("A,n_table,bl,nh,hd,lengths,trash", KERNEL_CASES)
def test_paged_attention_kernel_parity_float(A, n_table, bl, nh, hd,
                                             lengths, trash):
    """Pallas kernel (interpret mode on CPU) vs the XLA oracle: the
    ``impl="auto"`` dispatch contract from quant_matmul, paged."""
    from analytics_zoo_tpu.ops.paged_attention import paged_attention
    q, _, _, kp, vp, tables, lens = _pool_case(1, A, n_table, bl, nh, hd,
                                               lengths, trash)
    kp, vp = _fold(kp), _fold(vp)
    oracle = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                        impl="xla"))
    kern = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                      impl="interpret"))
    np.testing.assert_allclose(kern, oracle, rtol=2e-5, atol=2e-5)
    _check_trash_rows(kern, oracle, trash)


@pytest.mark.parametrize("A,n_table,bl,nh,hd,lengths,trash", KERNEL_CASES)
def test_paged_attention_kernel_parity_int8(A, n_table, bl, nh, hd,
                                            lengths, trash):
    from analytics_zoo_tpu.inference.quantize import kv_pack_int8
    from analytics_zoo_tpu.ops.paged_attention import (paged_attention,
                                                       paged_attention_xla)
    q, _, _, kp, vp, tables, lens = _pool_case(2, A, n_table, bl, nh, hd,
                                               lengths, trash)
    qk, ks = kv_pack_int8(kp)
    qv, vs = kv_pack_int8(vp)
    qk, qv = _fold(qk), _fold(qv)
    oracle = np.asarray(paged_attention_xla(q, qk, qv, tables, lens,
                                            k_scale=ks, v_scale=vs))
    kern = np.asarray(paged_attention(q, qk, qv, tables, lens,
                                      k_scale=ks, v_scale=vs,
                                      impl="interpret"))
    np.testing.assert_allclose(kern, oracle, rtol=2e-5, atol=2e-5)
    _check_trash_rows(kern, oracle, trash)
    # the quantization itself stays close to the float answer
    flt = np.asarray(paged_attention_xla(q, _fold(kp), _fold(vp), tables,
                                         lens))
    np.testing.assert_allclose(oracle, flt, atol=0.15)


@pytest.mark.parametrize("bl,n_table,width,itemsize,want", [
    (16, 64, 1280, 4, 8),       # the cells: 128 positions a step
    (16, 8, 768, 4, 8),         # chip_smoke, GPT-2-small width
    (8, 4, 16, 4, 4),           # a table narrower than a group is one
    (8, 20, 16, 4, 16),         # ... and a longer one is padded into groups
    (16, 64, 1280, 1, 8),       # int8 pools: the same rows
    (16, 64, 8192, 4, 2),       # fetch buffers held to the VMEM budget
    (256, 8, 1280, 4, 1),       # a block longer than a group: one a step
])
def test_paged_attention_group_follows_shapes(bl, n_table, width, itemsize,
                                              want):
    from analytics_zoo_tpu.ops.paged_attention import _group_blocks
    assert _group_blocks(bl, n_table, width, itemsize) == want


@pytest.mark.parametrize("contract", [((1,), (0,)), ((1,), (1,))],
                         ids=["reduce", "spread"])
def test_paged_attention_01_matmul_keeps_float32(contract):
    """The three-bf16-pass contraction with the 0/1 head matrix is as
    exact as a float32 one (a single bf16 pass is off by ~4e-3)."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.paged_attention import (_dot01,
                                                       _head_segments)
    nh, hd = 4, 8
    seg = _head_segments(nh, hd)                       # (32, 128) bf16
    g = np.random.default_rng(5)
    x = (g.normal(size=(48, seg.shape[contract[1][0]]))
         * 10.0 ** g.integers(-3, 4, size=(48, 1))).astype(np.float32)
    got = np.asarray(_dot01(jnp.asarray(x), seg, contract))
    seg64 = np.asarray(seg, np.float64)
    want = x.astype(np.float64) @ (seg64 if contract[1] == (0,) else seg64.T)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-30)


# -- scheduler end-to-end -----------------------------------------------------

GEO = dict(max_active_slots=4, max_tokens=5, max_prompt_len=24,
           stream_interval=0, decode_quantum=2)


def test_paged_float_tokens_exactly_match_monolithic():
    im, _ = _im()
    reqs = _shared_reqs()
    mono = _drive(_batcher(im, **GEO), reqs, "m-")
    paged = _batcher(im, paged=True, block_len=8, **GEO)
    out = _drive(paged, reqs, "p-")
    for rid, _, _ in reqs:
        assert out[rid] == mono[rid], \
            f"{rid}: paged {out[rid]} != monolithic {mono[rid]}"
    pool = paged.stats()["pool"]
    assert pool["prefix_hits"] > 0, \
        f"shared-prompt mix produced no prefix hits: {pool}"
    assert pool["exhausted"] == 0


def test_paged_int8_first_tokens_match():
    """int8 decode reads quantized KV, so full sequences may diverge
    (documented tolerance); first tokens come from the float prefill and
    must agree."""
    im, _ = _im()
    reqs = _shared_reqs()
    mono = _drive(_batcher(im, **GEO), reqs, "m-")
    out = _drive(_batcher(im, paged=True, block_len=8, kv_quant="int8",
                          **GEO), reqs, "q-")
    first = sum(out[rid][0] == mono[rid][0] for rid, _, _ in reqs)
    assert first == len(reqs), f"{first}/{len(reqs)} first tokens matched"


def test_paged_pool_blocks_return_after_drain():
    im, _ = _im()
    b = _batcher(im, paged=True, block_len=8, prefix_cache=False, **GEO)
    _drive(b, _shared_reqs(), "d-")
    pool = b.stats()["pool"]
    assert pool["free_blocks"] == pool["blocks"], \
        f"leaked blocks after drain: {pool}"
    assert b.active == 0


def test_pool_exhaustion_sheds_to_recorder_and_recovers():
    from analytics_zoo_tpu.common.observability import get_recorder
    im, _ = _im()
    # a pool that fits ONE resident request: admission must stall (typed
    # event, counter) yet every request still completes
    b = _batcher(im, paged=True, block_len=8, pool_blocks=4,
                 prefix_cache=False, **GEO)
    n0 = len(get_recorder().events("kv_pool_exhausted"))
    out = _drive(b, _shared_reqs(n=6), "x-")
    assert len(out) == 6
    assert b.pool_exhausted > 0
    assert b.stats()["pool"]["exhausted"] == b.pool_exhausted
    evs = get_recorder().events("kv_pool_exhausted")[n0:]
    assert evs, "exhaustion did not reach the flight recorder"
    assert {"rid", "need_blocks", "free_blocks", "active_slots",
            "waiting"} <= set(evs[0])


def test_paged_zero_steady_compiles_after_warm():
    from analytics_zoo_tpu.inference import aot
    im, _ = _im()
    b = _batcher(im, paged=True, block_len=8, **GEO)
    b.warm()
    _drive(b, _shared_reqs(), "w0-")       # absorbs admission-mix luck
    c0 = aot.COMPILE_STATS.snapshot()
    _drive(b, _shared_reqs(), "w1-")
    c1 = aot.COMPILE_STATS.snapshot()
    assert c1["compile_requests"] == c0["compile_requests"], \
        "steady-state paged traffic compiled"


# -- the seam between scheduler and model (PR 30) -----------------------------

def _fused_kv_lm(**kw):
    """A model that keeps the paged contract with ANOTHER state layout:
    one fused (n_blocks, block_len, 2, hidden) K|V array a layer, plus a
    per-slot leaf that is no KV at all (a stand-in for recurrent state:
    how many positions each slot has seen).  The math is ``TransformerLM``'s,
    so its tokens are known."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.textmodels import TransformerLM

    class FusedKVLM(TransformerLM):
        @staticmethod
        def _split(state):
            return {"k": [kv[:, :, 0] for kv in state["kv"]],
                    "v": [kv[:, :, 1] for kv in state["kv"]]}

        @staticmethod
        def _fuse(pools, seen):
            return {"kv": [jnp.stack([k, v], axis=2)
                           for k, v in zip(pools["k"], pools["v"])],
                    "seen": seen}

        def init_paged_pools(self, n_blocks, block_len, max_active,
                             kv_quant="off"):
            assert kv_quant == "off"
            return {"kv": [np.zeros((n_blocks, block_len, 2, self.hidden),
                                    np.float32)
                           for _ in range(self.n_layers)],
                    "seen": np.zeros((max_active,), np.int32)}

        def paged_state_bytes(self, state):
            return {"paged_pool": sum(int(np.prod(kv.shape)) * 4
                                      for kv in state["kv"]),
                    "lanes": int(np.prod(state["seen"].shape)) * 4}

        def prefill_paged(self, params, state, prompt, lengths, dest,
                          slots, **fmt):
            pools, logits0 = super().prefill_paged(
                params, self._split(state), prompt, lengths, dest, slots,
                **fmt)
            seen = state["seen"].at[slots].set(lengths, mode="drop")
            return self._fuse(pools, seen), logits0

        def prefill_shared_paged(self, params, state, suffix, lengths,
                                 prefix_len, ptab, dest, slots, **fmt):
            pools, logits0 = super().prefill_shared_paged(
                params, self._split(state), suffix, lengths, prefix_len,
                ptab, dest, slots, **fmt)
            seen = state["seen"].at[slots].set(prefix_len + lengths,
                                               mode="drop")
            return self._fuse(pools, seen), logits0

        def decode_paged(self, params, state, block_tables, pos, tokens,
                         **fmt):
            logits, pools = super().decode_paged(
                params, self._split(state), block_tables, pos, tokens,
                **fmt)
            return logits, self._fuse(pools, state["seen"] + 1)

    return FusedKVLM(**kw)


def test_scheduler_serves_a_model_with_another_state_layout():
    """``ContinuousBatcher(paged=True)`` unmodified over ``_fused_kv_lm``:
    warm-up, admission in batches (prefix hits among them), decode,
    finish, pool release, the ledger — the scheduler names no leaf of the
    state, so the tokens are ``TransformerLM``'s, nothing compiles after
    warm-up and ``pdecode`` takes every leaf over in place."""
    import jax
    from analytics_zoo_tpu.inference import aot
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    im, lm = _im(n_layers=2)
    fused = _fused_kv_lm(vocab_size=64, hidden=32, n_head=2, n_layers=2,
                         max_len=64)
    fim = InferenceModel().do_load_model(fused, im._params, {})
    reqs = _shared_reqs()
    want = _drive(_batcher(im, paged=True, block_len=8, **GEO), reqs, "t-")
    b = _batcher(fim, paged=True, block_len=8, **GEO)
    lane = b._lanes[0]
    assert set(lane.state) == {"kv", "seen"}
    doc = b.warm()
    assert doc["failed"] == 0, doc["errors"]
    assert _drive(b, reqs, "f0-") == want
    c0 = aot.COMPILE_STATS.snapshot()
    assert _drive(b, reqs, "f1-") == want
    assert aot.COMPILE_STATS.snapshot()["compile_requests"] \
        == c0["compile_requests"], "steady-state traffic compiled"
    s = b.stats()
    assert s["pool"]["prefix_hits"] > 0 and s["quarantined"] == 0
    assert b.active == 0
    b._prefix.clear()           # what is still held is the prefix index's
    assert b.stats()["pool"]["free_blocks"] == s["pool"]["blocks"]
    # the state is the model's: its extra leaf lived through every program
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(lane.state))
    assert int(np.asarray(lane.state["seen"]).max()) > 0
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(lane.state))
    assert b._record_of[("pdecode", lane.bucket)]["alias_bytes"] \
        == nbytes == lane.state_nbytes
    assert s["state_bytes_aliased"] == s["boundaries"] * nbytes
    n_blocks = b._pool.n_blocks + 1
    kv = 2 * n_blocks * 8 * 2 * 32 * 4
    ledger = b.state_bytes_doc()
    assert (ledger["paged_pool"], ledger["scales"], ledger["lanes"]) \
        == (kv, 0, b.gen.max_active_slots * 4)
    assert ledger["total"] == kv + ledger["lanes"] + ledger["aux"]


@pytest.mark.parametrize("lengths", [(8, 8), (5, 8), (3, 1)],
                         ids=["full", "ragged", "short"])
def test_the_five_forward_paths_agree(lengths):
    """One decoder block, five attention steps: ``call``'s logits at a
    row's last position = ``init_decode``'s ``logits0`` =
    ``prefill_paged``'s, and ``decode_step`` = ``decode_paged`` logit for
    logit over 8 steps (float pool, XLA reference, bitwise on the CPU)."""
    import jax
    import jax.numpy as jnp
    _, lm = _im(n_layers=2)
    params = lm.build(jax.random.PRNGKey(3))
    B, P, bl, ntab = 2, 8, 4, 8
    g = np.random.default_rng(7)
    prompt = g.integers(1, 64, (B, P)).astype(np.int32)
    lens = np.asarray(lengths, np.int32)
    full = np.asarray(lm.call(params, prompt))
    state, logits0 = lm.init_decode(params, prompt, lens,
                                    cache_len=ntab * bl)
    tables = 1 + np.arange(B * ntab, dtype=np.int32).reshape(B, ntab)
    pools = jax.device_put(lm.init_paged_pools(1 + B * ntab, bl, B))
    pstate, plogits0 = lm.prefill_paged(
        params, pools, prompt, lens, tables[:, :P // bl],
        np.arange(B, dtype=np.int32), block_len=bl)
    np.testing.assert_array_equal(np.asarray(logits0),
                                  full[np.arange(B), lens - 1])
    np.testing.assert_array_equal(np.asarray(plogits0), np.asarray(logits0))
    tok, pos = jnp.argmax(logits0, axis=-1).astype(jnp.int32), lens
    for step in range(8):
        logits, state = lm.decode_step(params, state, tok)
        plogits, pstate = lm.decode_paged(params, pstate, tables, pos, tok,
                                          block_len=bl, impl="xla")
        np.testing.assert_array_equal(
            np.asarray(plogits), np.asarray(logits),
            err_msg=f"decode_paged left decode_step at step {step}")
        tok, pos = jnp.argmax(logits, axis=-1).astype(jnp.int32), pos + 1


# -- ledger golden (the state_bytes aux bugfix) -------------------------------

def _expect_paged_bytes(lm, gen, n_pool_total):
    L, nh = lm.n_layers, lm.n_head
    hd = lm.hidden // nh
    A, bl = gen.max_active_slots, gen.block_len
    ntab = 32 // bl                       # GEO bucket: pow2(24 + 5) = 32
    itemsize = 1 if gen.kv_quant == "int8" else 4
    pool = 2 * L * n_pool_total * bl * nh * hd * itemsize
    scales = 2 * L * n_pool_total * nh * 4 if gen.kv_quant == "int8" else 0
    lanes = 2 * L * A * bl * nh * hd * 4 if gen.kv_quant == "int8" else 0
    aux = A * 4 + A * ntab * 4 + A * 4
    return {"lanes": lanes, "paged_pool": pool, "scales": scales,
            "aux": aux, "total": lanes + pool + scales + aux}


@pytest.mark.parametrize("kv_quant", ["off", "int8"])
def test_state_bytes_golden(kv_quant):
    from analytics_zoo_tpu.inference.resources import ResourceLedger
    im, lm = _im()
    b = _batcher(im, paged=True, block_len=8, kv_quant=kv_quant, **GEO)
    n_pool_total = b._pool.n_blocks + 1   # + the reserved trash block
    want = _expect_paged_bytes(lm, b.gen, n_pool_total)
    assert b.state_bytes_doc() == want
    assert b.state_bytes() == want["total"]
    # the ledger reads the same numbers (satellite 1: ledger bytes ==
    # exact pool + lane tree bytes)
    led = ResourceLedger(im, b)
    assert led.kv_state_bytes() == want["total"]
    doc = led.doc()
    assert doc["kv_state"] == want
    assert doc["kv_state_bytes"] == want["total"]


def test_state_bytes_counts_aux_for_monolithic_lanes():
    """The satellite-1 bugfix: per-slot host-side scheduler state (token
    cursors) is part of the footprint even for monolithic lanes."""
    im, _ = _im()
    b = _batcher(im, **GEO)
    doc = b.state_bytes_doc()
    assert doc["aux"] == b.gen.max_active_slots * 4
    assert doc["paged_pool"] == 0 and doc["scales"] == 0
    assert doc["total"] == doc["lanes"] + doc["aux"]
    assert b.state_bytes() == doc["total"]


def test_int8_paged_halves_kv_bytes():
    # realistic lane capacity (bucket 64): the int8 staging buffers are
    # O(slots * block_len) FIXED cost, so a toy-short lane understates
    # the pool ratio the acceptance measures
    geo = dict(GEO, max_tokens=40)
    im, _ = _im()
    mono = _batcher(im, **geo).state_bytes()
    quant = _batcher(im, paged=True, block_len=8, kv_quant="int8",
                     **geo).state_bytes()
    assert mono / quant >= 2.0, \
        f"int8+paged ratio {mono / quant:.2f} below 2x (mono={mono}, " \
        f"paged={quant})"


# -- warm-up manifest ---------------------------------------------------------

def test_warmup_manifest_paged_entries():
    im, _ = _im()
    b = _batcher(im, paged=True, block_len=8, **GEO)
    entries = b.warmup_manifest()
    kinds = {e.kind for e in entries}
    assert kinds == {"paged_decode", "paged_prefill", "paged_shared"}
    shared = [e for e in entries if e.kind == "paged_shared"]
    # prompt_max 24 / block_len 8 -> up to 2 shareable full blocks
    assert sorted({e.prefix_blocks for e in shared}) == [1, 2]
    # warming the set compiles every program key the live path uses
    b.warm()
    live = {k[0] for k in b._programs if k and k[0] not in ("fns", "pfns")}
    assert live == {"pprefill", "pshared", "pdecode"}
    # the cached jit closures are NOT programs: program_stats must not
    # count the ("pfns",) entry
    assert b.program_stats()["count"] == len(b._programs) - 1


def test_generation_manifest_non_paged_unchanged():
    from analytics_zoo_tpu.inference.aot import generation_manifest
    entries = generation_manifest([8, 16], [32], prefill_batches=(1, 2))
    assert all(not e.kind.startswith("paged_") for e in entries)
    assert all(e.prefix_blocks is None for e in entries)
    paged = generation_manifest([8], [32], paged=True, prefix_blocks=(1,))
    assert {e.kind for e in paged} == {"paged_decode", "paged_prefill",
                                       "paged_shared"}


# -- fleet aggregation --------------------------------------------------------

def test_fleet_aggregates_kv_pool():
    from analytics_zoo_tpu.serving.fleet import aggregate_health

    def doc(free, hits):
        return {"running": True,
                "generation": {"active_slots": 2,
                               "pool": {"blocks": 16, "free_blocks": free,
                                        "used_blocks": 16 - free,
                                        "prefix_hits": hits,
                                        "prefix_misses": 4,
                                        "prefix_evictions": 1,
                                        "exhausted": 1}}}

    agg = aggregate_health({0: doc(10, 3), 1: doc(4, 5)})
    kv = agg["kv_pool"]
    assert kv["blocks"] == 32 and kv["free_blocks"] == 14
    assert kv["used_blocks"] == 18 and kv["prefix_hits"] == 8
    assert kv["exhausted"] == 2 and kv["active_slots"] == 4
    assert kv["occupancy"] == round(18 / 32, 4)
    # a fleet with no paged replica reports None, not zeros
    assert aggregate_health({0: {"running": True}})["kv_pool"] is None


# -- bench smoke --------------------------------------------------------------

def test_bench_paged_smoke(tmp_path):
    """The PR 18 acceptance bench, tier-1 geometry: int8+paged vs float
    monolithic — asserts inside the bench cover >= 2x ledger HBM ratio,
    prefix hits, token parity and zero steady-state compiles."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "serving_bench", os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "serving_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.main(["--model", "seq2seq", "--generate", "--paged", "on",
                    "--kv-quant", "int8", "--smoke",
                    "--json", str(tmp_path / "paged.json")])
    assert out["mode"] == "generate-paged"
    assert out["hbm_ratio"] >= 2.0
    assert out["paged"]["steady_compile_requests"] == 0
    assert out["paged"]["prefix_hit_rate"] > 0
    assert out["token_parity"]["first_token_match"] >= 0.9
    assert (tmp_path / "paged.json").exists()
