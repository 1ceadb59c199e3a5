"""``models/latent_moe_lm.LatentMoELM`` (PR 32) against its plain reference
(``benchmark/reference_latent_moe.py``, which calls no model code), at tiny
widths in FLOAT32 on the CPU: served through an unmodified
``ContinuousBatcher(paged=True)``, logit for logit; the absorbed decode path
against the expanded one; the selection against dense attention and against
the reference's top-k; the expert shares against the uncut layer; no pair
dropped at the worst imbalance; the device counters against counts taken from
the reference's routing and selection.

TOLERANCE.  Both sides compute in float32 (the reference at ``highest``), so
they differ by summation order only: logits of magnitude ~7 agree to ~1e-5
(measured 8.6e-6).  ``LOGIT_TOL`` = 2e-4 leaves 20 x of room and is 100 x
under what the same model computed in bfloat16 reads (``test_a_lower_
precision_fails_the_tolerance``: > 2e-2), so a path that rounded anything to
bfloat16 would fail it.
"""
import functools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

LOGIT_TOL = 2e-4
TOPK = 6
# the configuration file's shape: published keys as cut, the published value
# of what was cut, the share: chip 1 of 2 holds experts 4..7 of 8
CFG = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=48, moe_intermediate_size=16,
    n_routed_experts=4, num_experts_per_tok=3, n_shared_experts=1,
    routed_scaling_factor=2.5, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    index_n_heads=4, index_head_dim=8, index_topk=TOPK, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000.0}, max_position_embeddings=64,
    published={"n_routed_experts": 8}, deployment={"chip": 1})


_BUILT = {}


def _lm(cls=None, **over):
    """``(model, weights, configuration)``, built once a configuration."""
    import json

    import jax
    from analytics_zoo_tpu.models.latent_moe_lm import LatentMoELM
    cfg = dict(CFG, **over)
    key = (cls, json.dumps(cfg, sort_keys=True))
    if key not in _BUILT:
        lm = (cls or LatentMoELM).from_config(cfg, dtype="float32",
                                              initializer_range=0.3)
        _BUILT[key] = lm, jax.jit(lm.build)(jax.random.PRNGKey(0)), cfg
    return _BUILT[key]


def _ref_logits(params, cfg, ids, rows=None, **kw):
    """The reference over ``ids`` right-padded to ONE length (causal
    attention makes the padding harmless), so that its layers compile once
    a configuration."""
    import reference_latent_moe as ref
    padded = np.zeros((48,), np.int32)
    padded[:len(ids)] = ids
    return ref.logits(params, cfg, padded,
                      np.arange(len(ids)) if rows is None else rows, **kw)


def _spy_class():
    """The model with two more leaves in its state: the logits of every
    token it served, by (slot, position), and the prompt length of the
    request a slot last took.  The scheduler passes the state through as it
    passes the pool."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.latent_moe_lm import LatentMoELM

    class Spy(LatentMoELM):
        def init_paged_pools(self, n_blocks, block_len, max_active,
                             kv_quant="off"):
            return dict(super().init_paged_pools(n_blocks, block_len,
                                                 max_active, kv_quant),
                        seen=np.zeros((max_active, self.max_len,
                                       self.vocab_size), np.float32),
                        who=np.zeros((max_active,), np.int32))

        def paged_state_bytes(self, state):
            out = super().paged_state_bytes(self._own(state))
            out["lanes"] += 4 * int(np.prod(state["seen"].shape)
                                    + state["who"].shape[0])
            return out

        @staticmethod
        def _own(state):
            return {k: v for k, v in state.items()
                    if k not in ("seen", "who")}

        def prefill_paged(self, params, state, prompt, lengths, dest, slots,
                          **kw):
            st, logits0 = super().prefill_paged(
                params, self._own(state), prompt, lengths, dest, slots, **kw)
            return dict(
                st, who=state["who"].at[slots].set(lengths, mode="drop"),
                seen=state["seen"].at[slots, lengths - 1].set(
                    logits0, mode="drop")), logits0

        def decode_paged(self, params, pstate, block_tables, pos, tokens,
                         **kw):
            seen = pstate["seen"]
            logits, st = super().decode_paged(
                params, self._own(pstate), block_tables, pos, tokens, **kw)
            rows = jnp.where(block_tables[:, 0] != 0,
                             jnp.arange(pos.shape[0]), seen.shape[0])
            return logits, dict(st, who=pstate["who"], seen=seen.at[
                rows, pos].set(logits, mode="drop"))

    return Spy


@pytest.fixture(scope="module")
def served():
    """Four requests (contexts under and over ``index_topk``) through an
    unmodified ``ContinuousBatcher``: ``(batcher, lm, params, cfg, [(prompt,
    tokens, slot)])`` after warm-up and two passes."""
    import jax
    from analytics_zoo_tpu.inference import aot
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams,
                                                    GenRequest)
    lm, params, cfg = _lm(_spy_class())
    im = InferenceModel().do_load_model(lm, params, {})
    b = ContinuousBatcher(im, GenerationParams(
        max_active_slots=4, max_prompt_len=30, max_tokens=9,
        prefill_buckets=[32], paged=True, block_len=4, decode_quantum=2,
        prefix_cache=False, stream_interval=0))
    doc = b.warm()
    assert doc["failed"] == 0, doc["errors"]
    g = np.random.default_rng(5)
    # budgets b with (b - 1) % decode_quantum == 0: no row-step is wasted, so
    # the device counts exactly the tokens the requests needed
    reqs = [(f"r{i}", g.integers(1, 97, n).astype(np.int32), budget)
            for i, (n, budget) in enumerate([(3, 3), (5, 9), (17, 5),
                                             (30, 7)])]
    c0 = aot.COMPILE_STATS.snapshot()
    runs = []
    for tag in ("a-", "b-"):
        for rid, prompt, budget in reqs:
            assert b.submit(GenRequest(tag + rid, prompt, max_tokens=budget))
        done = {}
        for _ in range(1000):
            for ev in b.step():
                assert ev.kind not in ("shed", "quarantine"), ev.error
                if ev.kind == "finish":
                    done[ev.rid] = list(ev.tokens)
            if len(done) == len(reqs):
                break
        who = list(np.asarray(b._lanes[0].state["who"]))
        runs.append([(p, done[tag + rid], who.index(len(p)))
                     for rid, p, _ in reqs])
    assert aot.COMPILE_STATS.snapshot()["compile_requests"] \
        == c0["compile_requests"], "traffic compiled after the warm-up"
    assert [t for _, t, _ in runs[0]] == [t for _, t, _ in runs[1]]
    lane = b._lanes[0]
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(lane.state))
    return b, lm, params, cfg, runs[1]


# -- (a) served logits ---------------------------------------------------------

@pytest.mark.parametrize("i", range(4), ids=["ctx3", "ctx5", "ctx17",
                                             "ctx30"])
def test_served_logits_equal_the_references_full_forward(served, i):
    """Prefill then paged decode through the unmodified scheduler, against
    the reference's teacher-forced forward of prompt + served tokens, at
    EVERY served position (contexts 3-38 against ``index_topk`` 6)."""
    b, _, params, cfg, runs = served
    prompt, tokens, slot = runs[i]
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    rows = np.arange(len(prompt) - 1, len(ids) - 1)
    want = _ref_logits(params, cfg, ids, rows)
    got = np.asarray(b._lanes[0].state["seen"])[slot, rows]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert list(want.argmax(-1)) == tokens


def test_the_state_is_donated_and_sorted_into_the_ledger(served):
    b, lm, _, _, _ = served
    lane = b._lanes[0]
    assert set(lane.state) == {"kv", "ik", "counters", "seen", "who"}
    assert b._record_of[("pdecode", lane.bucket)]["alias_bytes"] \
        == lane.state_nbytes
    n_blocks = b._pool.n_blocks + 1
    doc = b.state_bytes_doc()
    assert doc["paged_pool"] == 3 * n_blocks * 4 * (128 + 8) * 4
    assert doc["lanes"] == 9 * 2 * 4 + 4 * (64 * 97 + 1) * 4
    assert doc["scales"] == 0


# -- (f) counters --------------------------------------------------------------

def test_model_counters_equal_counts_from_the_reference(served):
    """``stats()["model.*"]`` over the two passes = twice what the
    reference's routing and selection give for one: all four requests are
    admitted at the first boundary, so decode step j holds the rows whose
    budget needs it."""
    import reference_latent_moe as ref
    b, lm, params, cfg, runs = served
    first, count, _ = ref.held_experts(cfg)
    k = cfg["num_experts_per_tok"]
    want = dict.fromkeys(
        ("moe_pairs", "moe_pairs_held", "moe_pairs_busiest",
         "moe_experts_touched", "moe_layer_steps", "dsa_keys_selected",
         "dsa_keys_context"), 0)
    steps = {}                      # (decode step, layer) -> chosen rows
    for prompt, tokens, _ in runs:
        ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        probe = []
        _ref_logits(params, cfg, ids, [0], probe=probe)
        for li, seen in enumerate(probe):
            for j in range(len(tokens) - 1):
                t = len(prompt) + j
                want["dsa_keys_selected"] += int(seen["allowed"][t])
                want["dsa_keys_context"] += t + 1
                if seen["chosen"] is not None:
                    steps.setdefault((j, li), []).append(seen["chosen"][t])
            if seen["chosen"] is None:
                continue
            want["moe_pairs"] += len(ids) * k
            mine = seen["chosen"][:len(prompt)] - first   # the prefill call
            sizes = np.bincount(mine[(mine >= 0) & (mine < count)],
                                minlength=count)
            want["moe_pairs_held"] += int(sizes.sum())
            want["moe_pairs_busiest"] += int(sizes.max())
    for rows in steps.values():
        mine = np.concatenate(rows) - first
        sizes = np.bincount(mine[(mine >= 0) & (mine < count)],
                            minlength=count)
        want["moe_pairs_held"] += int(sizes.sum())
        want["moe_pairs_busiest"] += int(sizes.max())
        want["moe_experts_touched"] += int((sizes > 0).sum())
        want["moe_layer_steps"] += 1
    stats = b.stats()
    assert {n: stats["model." + n] for n in want} \
        == {n: 2 * v for n, v in want.items()}
    assert stats["model.dsa_keys_selected"] < stats["model.dsa_keys_context"]
    assert b.model_counters == lm.paged_counters(b._lanes[0].state)


def test_a_model_without_counters_publishes_none():
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams)
    import jax
    lm = TransformerLM(vocab_size=64, hidden=32, n_head=2, n_layers=1,
                       max_len=64)
    im = InferenceModel().do_load_model(lm, lm.build(jax.random.PRNGKey(0)),
                                        {})
    for paged in (True, False):
        b = ContinuousBatcher(im, GenerationParams(
            max_prompt_len=24, max_tokens=5, paged=paged, block_len=8))
        assert b.model_counters == {}
        assert not [n for n in b.stats() if n.startswith("model.")]


def test_counters_carry_past_32_bits():
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.latent_moe_lm import COUNTERS, LatentMoELM
    c = jnp.zeros((len(COUNTERS), 2), jnp.int32)
    step = jnp.full((len(COUNTERS),), (1 << 30) - 1, jnp.int32)
    for _ in range(9):
        c = LatentMoELM._bump(c, step)
    lm, _, _ = _lm()
    assert set(lm.paged_counters({"counters": c}).values()) \
        == {9 * ((1 << 30) - 1)}


# -- (b) absorbed = expanded, and the paths a test alone runs ------------------

def _prefilled(lm, params, prompt, lens, bl=4, ntab=10):
    import jax
    B, P = prompt.shape
    tables = 1 + np.arange(B * ntab, dtype=np.int32).reshape(B, ntab)
    dest = np.where(np.arange(P // bl)[None] * bl < lens[:, None],
                    tables[:, :P // bl], 0)
    state = jax.device_put(lm.init_paged_pools(1 + B * ntab, bl, B))
    state, logits0 = jax.jit(functools.partial(
        lm.prefill_paged, block_len=bl))(params, state, prompt, lens, dest,
                                         np.arange(B))
    return state, tables, logits0


def test_absorbed_decode_equals_expanded_prefill():
    """``decode_paged`` (absorbed, selected rows gathered through the block
    table) logit for logit against ``call`` (expanded, masked dense scores)
    over the same tokens, 10 steps, contexts 5-26 around ``index_topk``."""
    _absorbed_against_expanded(impl=None)


def test_absorbed_decode_through_the_expert_kernel_equals_expanded_prefill():
    """The same with ``impl="interpret"``: the decode step's routed experts
    through ``ops/expert_matmul``'s kernel (a share of 4 of 8 experts held,
    so pairs land off the chip), against ``call``'s ``ragged_dot``."""
    _absorbed_against_expanded(impl="interpret")


def _absorbed_against_expanded(impl):
    import jax
    lm, params, _ = _lm()
    g = np.random.default_rng(1)
    prompt = g.integers(1, 97, (2, 16)).astype(np.int32)
    lens = np.array([16, 5], np.int32)
    state, tables, logits0 = _prefilled(lm, params, prompt, lens)
    seqs = [list(prompt[r, :lens[r]]) for r in range(2)]
    dec = jax.jit(lambda s, po, tk: lm.decode_paged(params, s, tables, po,
                                                    tk, block_len=4,
                                                    impl=impl))
    got, tok, pos = [np.asarray(logits0)], np.asarray(logits0).argmax(-1), lens
    for _ in range(10):
        for r in range(2):
            seqs[r].append(int(tok[r]))
        logits, state = dec(state, pos, tok.astype(np.int32))
        got.append(np.asarray(logits))
        tok, pos = np.asarray(logits).argmax(-1), pos + 1
    for r in range(2):
        full = np.zeros((1, 32), np.int32)
        full[0, :len(seqs[r])] = seqs[r]
        want = np.asarray(lm.call(params, full))[0]
        for j, logits in enumerate(got):
            np.testing.assert_allclose(logits[r], want[lens[r] - 1 + j],
                                       atol=LOGIT_TOL, rtol=0)


def test_contiguous_and_shared_prefix_paths_agree_with_the_paged_ones():
    """``init_decode`` / ``decode_step`` (what ``ContinuousBatcher.__init__``
    asks of every model) and ``prefill_shared_paged`` (suffix-only prefill
    over pool-resident prefix blocks): no cell runs them, this test does."""
    import jax
    lm, params, _ = _lm()
    g = np.random.default_rng(2)
    prompt = g.integers(1, 97, (2, 16)).astype(np.int32)
    lens = np.array([13, 16], np.int32)
    state, tables, logits0 = _prefilled(lm, params, prompt, lens)
    cstate, clogits0 = jax.jit(functools.partial(
        lm.init_decode, cache_len=32))(params, prompt, lens)
    np.testing.assert_allclose(np.asarray(clogits0), np.asarray(logits0),
                               atol=LOGIT_TOL, rtol=0)
    tok, pos = np.asarray(logits0).argmax(-1).astype(np.int32), lens
    paged_step = jax.jit(functools.partial(lm.decode_paged, block_len=4))
    step = jax.jit(lm.decode_step)
    for _ in range(3):
        logits, state = paged_step(params, state, tables, pos, tok)
        clogits, cstate = step(params, cstate, tok)
        np.testing.assert_allclose(np.asarray(clogits), np.asarray(logits),
                                   atol=LOGIT_TOL, rtol=0)
        tok, pos = np.asarray(logits).argmax(-1).astype(np.int32), pos + 1
    # rows share their first 8 tokens' blocks (2 blocks of row 0's prefill)
    # and prefill only what follows, into blocks of their own
    fresh = jax.device_put(lm.init_paged_pools(1 + 20, 4, 2))
    fresh = dict(fresh, kv=[p.at[1:3].set(q[1:3]) for p, q in
                            zip(fresh["kv"], state["kv"])],
                 ik=[p.at[1:3].set(q[1:3]) for p, q in
                     zip(fresh["ik"], state["ik"])])
    suffix = np.zeros((2, 8), np.int32)
    suffix[0, :5], suffix[1] = prompt[0, 8:13], prompt[0, 8:16]
    _, shared0 = jax.jit(functools.partial(
        lm.prefill_shared_paged, block_len=4))(
        params, fresh, suffix, np.array([5, 8]), np.array([8, 8]),
        np.array([[1, 2], [1, 2]]), np.array([[11, 12], [13, 14]]),
        np.arange(2))
    both = np.stack([prompt[0], prompt[0]])
    _, want = _prefilled(lm, params, both, np.array([13, 16]))[1:]
    np.testing.assert_allclose(np.asarray(shared0), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


# -- (c) the selection ---------------------------------------------------------

@pytest.mark.parametrize("key_chunk", [8, 4096], ids=["4chunks", "1chunk"])
def test_selection_off_is_dense_attention_and_on_is_not(monkeypatch,
                                                        key_chunk):
    """With ``index_topk`` >= the context the model equals the reference's
    dense MLA; with it smaller it equals the reference's top-k attention
    and differs from the dense one.  Over 32 keys in chunks of 8 (the
    running-maximum path a prefill of 8,192 takes, future chunks skipped)
    and in one chunk."""
    from analytics_zoo_tpu.models import latent_moe_lm
    monkeypatch.setattr(latent_moe_lm, "_KEY_CHUNK", key_chunk)
    ids = np.random.default_rng(3).integers(1, 97, (1, 32)).astype(np.int32)
    lm, params, cfg = _lm(index_topk=64)
    dense = np.asarray(lm.call(params, ids))[0]
    np.testing.assert_allclose(dense, _ref_logits(params, cfg, ids[0]),
                               atol=LOGIT_TOL, rtol=0)
    lm, params, cfg = _lm()
    sparse = np.asarray(lm.call(params, ids))[0]
    np.testing.assert_allclose(sparse, _ref_logits(params, cfg, ids[0]),
                               atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(sparse[:TOPK], dense[:TOPK], atol=LOGIT_TOL,
                               rtol=0)
    assert np.abs(sparse[TOPK:] - dense[TOPK:]).max() > 0.05


@pytest.mark.parametrize("k", [1, 6, 40])
def test_the_keys_used_are_the_top_k(k):
    """The prefill's threshold mask and the decode's ``lax.top_k`` pick the
    keys a stable descending sort of the reference picks, ties (equal
    scores: the earlier key first), short rows and masked keys included."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.latent_moe_lm import LatentMoELM
    g = np.random.default_rng(k)
    score = g.normal(size=(24, 40)).astype(np.float32)
    score[:, ::3] = np.round(score[:, ::3])          # ties, zeros, -0.0
    score[5] = 0.0
    ok = g.random((24, 40)) < 0.7
    ok[7] = False
    ok[8, 3:] = False
    masked = np.where(ok, score, -np.inf)
    want = np.zeros_like(ok)
    best = np.argsort(-masked, axis=-1, kind="stable")[:, :k]
    np.put_along_axis(want, best, True, axis=-1)
    want &= ok
    got = np.asarray(LatentMoELM._topk_mask(jnp.asarray(score),
                                            jnp.asarray(ok), k))
    np.testing.assert_array_equal(got, want)
    _, sel = jax.lax.top_k(jnp.asarray(masked), min(k, 40))
    picked = np.zeros_like(ok)
    np.put_along_axis(picked, np.asarray(sel), True, axis=-1)
    np.testing.assert_array_equal(picked & ok, want)


# -- (c2) the decode selection computes its mask and row addresses (PR 34) ------

def _selection_draw(block_len, n_table):
    """Four rows over a pool whose every row holds its own flat address:
    shuffled block ids (up to 4 x ``n_table``: past 256 at the widest table),
    the last row an idle slot (its table all trash), and 24 selected
    positions a row with position 0, a block's last position, the row's own
    position and the lane's last position (beyond ``pos`` for three rows)
    among them."""
    g = np.random.default_rng(1000 * block_len + n_table)
    rows, S = 4, n_table * block_len
    n_blocks = 1 + rows * n_table
    pool = np.arange(n_blocks * block_len, dtype=np.float32)[:, None] \
        + np.arange(8, dtype=np.float32) / 8
    tables = (1 + g.permutation(rows * n_table)).reshape(rows, n_table) \
        .astype(np.int32)
    tables[-1] = 0
    pos = np.array([0, S // 2, S - 1, S // 3], np.int32)
    sel = np.stack([g.permutation(S)[:min(24, S)] for _ in range(rows)]) \
        .astype(np.int32)
    sel[:, 0], sel[:, 1], sel[:, 2], sel[:, 3] = 0, block_len - 1, pos, S - 1
    return pool.reshape(n_blocks, block_len, 8), tables, pos, sel


@pytest.mark.parametrize("n_table", [1, 8, 128])
@pytest.mark.parametrize("block_len", [16, 64])
def test_the_selection_computes_what_the_lookups_read(block_len, n_table):
    """``paged.latent_select`` (block id = the row's table summed under
    ``sel // block_len == arange(n_table)``) names the rows a NumPy walk of
    the block table names, and ``sel <= pos`` is ``arange(S) <= pos`` read
    at ``sel``: the same integers and booleans as the two
    ``take_along_axis`` of PR 32 on the same draws."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import paged_attention as paged
    pool, tables, pos, sel = _selection_draw(block_len, n_table)
    walked = np.stack([[pool[tables[r, s // block_len], s % block_len]
                        for s in sel[r]] for r in range(len(sel))])
    got = np.asarray(jax.jit(functools.partial(
        paged.latent_select, block_len=block_len))(pool, tables, sel))
    np.testing.assert_array_equal(got, walked)
    looked_up = jnp.take_along_axis(jnp.asarray(tables), sel // block_len,
                                    axis=1) * block_len + sel % block_len
    np.testing.assert_array_equal(got[..., 0], np.asarray(looked_up))
    assert got[-1, :, 0].max() < block_len    # the idle slot: trash rows
    in_ctx = jnp.arange(n_table * block_len)[None, :] <= pos[:, None]
    was = np.asarray(jnp.take_along_axis(in_ctx, sel, axis=1))
    np.testing.assert_array_equal(np.asarray(sel <= pos[:, None]), was)
    assert was[:, 2].all() and list(was[:, 3]) == [n_table * block_len == 1,
                                                   False, True, False]


def _lookup_oracle_class():
    """``LatentMoELM`` with PR 32's decode step: the selection's mask and
    block ids LOOKED UP by ``take_along_axis`` (what the package no longer
    does), the rest of ``decode_paged`` line for line."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.models import latent_moe_lm as mod
    from analytics_zoo_tpu.ops import paged_attention as paged

    class Oracle(mod.LatentMoELM):
        def decode_paged(self, params, state, block_tables, pos, tokens, *,
                         block_len, kv_quant="off", impl=None):
            nh, bl = self.n_head, int(block_len)
            bt = jnp.asarray(block_tables, jnp.int32)
            pos = jnp.asarray(pos, jnp.int32)
            cursor = paged.pool_cursor(bt, pos, bl)
            active = bt[:, 0] != 0
            S = bt.shape[1] * bl
            kk = min(self.index_topk, S)
            scale = self._softmax_scale()
            in_ctx = jnp.arange(S)[None, :] <= pos[:, None]

            def attend(li, blk, q, c_kv, k_r, q_i, k_i, w_i):
                kv_pool, ik_pool = paged.latent_append(
                    state, li, paged.latent_rows(c_kv, k_r, self.kv_width,
                                                 self.dtype), k_i, cursor)
                with mod._scope("dsa_index"):
                    keys = paged.latent_gather(ik_pool, bt)
                    dots = jax.nn.relu(self._ein("ajd,asd->ajs", q_i, keys))
                    score = jnp.einsum("ajs,aj->as", dots, w_i)
                with mod._scope("dsa_select"):
                    _, sel = jax.lax.top_k(
                        jnp.where(in_ctx, score, -jnp.inf), kk)
                    sel_ok = jnp.take_along_axis(in_ctx, sel, axis=1)
                    blk_id = jnp.take_along_axis(bt, sel // bl, axis=1)
                    rows = jnp.take(kv_pool.reshape(-1, kv_pool.shape[-1]),
                                    blk_id * bl + sel % bl, axis=0)
                with mod._scope("mla"):
                    c = rows[..., :self.kv_rank]
                    kr = rows[..., self.kv_rank:self.kv_rank + self.rope]
                    kvb = blk["kv_b"].reshape(self.kv_rank, nh,
                                              self.nope + self.v_dim)
                    q_abs = self._ein("ahd,rhd->ahr", q[..., :self.nope],
                                      kvb[..., :self.nope])
                    att = (self._ein("ahr,asr->ahs", q_abs, c)
                           + self._ein("ahd,asd->ahs", q[..., self.nope:],
                                       kr)) * scale
                    att = jnp.where(sel_ok[:, None], att, mod.NEG_INF)
                    p = jax.nn.softmax(att, axis=-1)
                    lat = self._ein("ahs,asr->ahr", p, c)
                    o = self._ein("ahr,rhd->ahd", lat, kvb[..., self.nope:])
                counts = mod._no_counts() \
                    .at[mod.COUNTERS.index("dsa_keys_selected")].set(
                        (sel_ok & active[:, None]).sum().astype(jnp.int32)) \
                    .at[mod.COUNTERS.index("dsa_keys_context")].set(
                        jnp.where(active, pos + 1, 0).sum().astype(
                            jnp.int32))
                return (o.reshape(-1, nh * self.v_dim), (kv_pool, ik_pool),
                        counts)

            x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                         axis=0).astype(jnp.float32)
            h, keeps, counts = self._blocks(params, x, pos, active, attend,
                                            decode=True)
            kvs, iks = map(list, zip(*keeps))
            return self._mm(h, params["head"]), dict(
                state, kv=kvs, ik=iks,
                counters=self._bump(state["counters"], counts))

    return Oracle


def _lookup_oracle():
    """The oracle at the toy configuration: ``_lm()``'s weights serve it."""
    return _lm(_lookup_oracle_class())[0]


def _decode_rows():
    """Three rows after a prefill, contexts 16, 3 and 9 around ``index_topk``
    6, the last one's slot idle since (its table all trash)."""
    lm, params, _ = _lm()
    prompt = np.random.default_rng(34).integers(1, 97, (3, 16)) \
        .astype(np.int32)
    lens = np.array([16, 3, 9], np.int32)
    state, tables, logits0 = _prefilled(lm, params, prompt, lens)
    tables = tables.copy()
    tables[2] = 0
    return (lm, params, state, tables, lens,
            np.asarray(logits0).argmax(-1).astype(np.int32))


def _gathers_by_stage(fn, *args):
    """``{stage: [(operand shape, operand dtype) of each gather]}`` over the
    jaxpr of ``fn`` and its sub-jaxprs; the stage is the innermost
    ``zoo.lm.<stage>`` scope, ``-`` outside every one."""
    import re

    import jax
    out = {}

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "gather":
                stage = re.findall(r"zoo\.lm\.(\w+)", stack)
                out.setdefault(stage[-1] if stage else "-", []).append(
                    (tuple(eqn.invars[0].aval.shape),
                     str(eqn.invars[0].aval.dtype)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, stack)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return out


def test_a_decode_layers_selection_holds_one_gather():
    """By stage and operand over the decode step's jaxpr:
    ``zoo.lm.dsa_select`` holds ONE gather a layer, the selected rows out of
    the ``kv`` pool laid flat; no gather anywhere reads booleans, and the
    block table is gathered once, outside every stage, by ``pool_cursor``
    (rotary's strided slices and the router's are gathers too, so the count
    is by stage).  The oracle holds the two lookups a layer more."""
    lm, params, state, tables, pos, tok = _decode_rows()
    L, kv = lm.n_layers, state["kv"][0].shape

    def gathers(model):
        return _gathers_by_stage(
            lambda p, s: model.decode_paged(p, s, tables, pos, tok,
                                            block_len=4), params, state)

    got, was = gathers(lm), gathers(_lookup_oracle())
    rows_read = ((kv[0] * kv[1], kv[2]), "float32")
    table_read = (tables.shape, "int32")
    flat = [op for ops in got.values() for op in ops]
    assert got["dsa_select"] == [rows_read] * L
    assert flat.count(table_read) == 1 and table_read in got["-"]
    assert not [op for op in flat if op[1] == "bool"]
    extra = list(was["dsa_select"])
    for op in got["dsa_select"]:
        extra.remove(op)
    assert sorted(extra) == sorted([(tables.shape[:1] + (tables.shape[1] * 4,),
                                     "bool"), table_read] * L)
    assert {k: v for k, v in was.items() if k != "dsa_select"} \
        == {k: v for k, v in got.items() if k != "dsa_select"}


@pytest.mark.parametrize("path", ["paged", "contiguous"])
def test_decode_is_the_lookup_oracle_bit_for_bit(path):
    """Four decode steps of the package's step and of the oracle that looks
    the mask and the block ids up: logits and every leaf of the state (both
    pools, the counters) are the same BITS, since only integers and booleans
    are come by another way.  ``paged``: contexts 16 and 3 crossing
    ``index_topk`` and an idle slot; ``contiguous``: ``decode_step``'s
    one-entry table."""
    import jax
    oracle = _lookup_oracle()
    if path == "paged":
        lm, params, state, tables, pos, tok = _decode_rows()

        def steps(model):
            f = jax.jit(functools.partial(model.decode_paged, block_len=4))
            return lambda st, po, tk: f(params, st, tables, po, tk)
    else:
        lm, params, _ = _lm()
        prompt = np.random.default_rng(35).integers(1, 97, (2, 16)) \
            .astype(np.int32)
        pos = np.array([16, 7], np.int32)      # the state carries its own
        state, logits0 = jax.jit(functools.partial(
            lm.init_decode, cache_len=32))(params, prompt, pos)
        tok = np.asarray(logits0).argmax(-1).astype(np.int32)

        def steps(model):
            f = jax.jit(model.decode_step)
            return lambda st, po, tk: f(params, st, tk)
    step, ostep, ostate = steps(lm), steps(oracle), state
    for _ in range(4):
        logits, state = step(state, pos, tok)
        ologits, ostate = ostep(ostate, pos, tok)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ologits))
        jax.tree_util.tree_map(np.testing.assert_array_equal, state, ostate)
        tok, pos = np.asarray(logits).argmax(-1).astype(np.int32), pos + 1
    if path == "paged":
        # a layer: min(context, 6) keys of the rows at 16.. and 3.., the
        # idle slot's none
        assert lm.paged_counters(state)["dsa_keys_selected"] \
            == lm.n_layers * (4 * 6 + (4 + 5 + 6 + 6))


# -- (c3) prefill runs only the query blocks a row has (PR 36) ------------------

def _unskipped_oracle():
    """``LatentMoELM`` with the query-block loop as it was before PR 36:
    EVERY block of the bucket is attended, the rest of ``_forward_row`` line
    for line (what the package no longer does).  ``_lm()``'s weights serve
    it."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.models import latent_moe_lm as mod
    from analytics_zoo_tpu.ops import paged_attention as paged

    class Oracle(mod.LatentMoELM):
        def _forward_row(self, params, ids, length, base=0, prefix=None,
                         counted=None):
            S = ids.shape[0]
            qb = min(mod._QUERY_BLOCK, S)
            nh, dt = self.n_head, self.dtype
            pos = base + jnp.arange(S)
            PL = 0 if prefix is None else prefix[0][0].shape[0]
            key_pos = jnp.concatenate([jnp.arange(PL), pos])
            key_ok = jnp.concatenate([jnp.arange(PL) < base,
                                      jnp.arange(S) < length])
            scale = self._softmax_scale()

            def attend(li, blk, q, c_kv, k_r, q_i, k_i, w_i):
                kv_rows = paged.latent_rows(c_kv, k_r, self.kv_width, dt)
                ik_rows = k_i.astype(dt)
                keys_kv, keys_ik = kv_rows, ik_rows
                if prefix is not None:
                    keys_kv = jnp.concatenate([prefix[0][li], kv_rows])
                    keys_ik = jnp.concatenate([prefix[1][li], ik_rows])
                c = keys_kv[:, :self.kv_rank]
                kr = keys_kv[:, self.kv_rank:self.kv_rank + self.rope]
                with mod._scope("mla"):
                    kvb = self._mm(c, blk["kv_b"], out=dt).reshape(
                        c.shape[0], nh, self.nope + self.v_dim)
                    k = jnp.concatenate(
                        [kvb[..., :self.nope],
                         jnp.broadcast_to(kr[:, None], (c.shape[0], nh,
                                                        self.rope))],
                        axis=-1)
                    v = kvb[..., self.nope:]

                def block(args):
                    qq, qi, wi, t = args
                    with mod._scope("dsa_index"):
                        score = self._index_scores(qi, keys_ik, wi)
                    with mod._scope("dsa_select"):
                        ok = (key_pos[None, :] <= t[:, None]) \
                            & key_ok[None, :]
                        allowed = self._topk_mask(score, ok, self.index_topk)
                    with mod._scope("mla"):
                        return self._attend_chunks(
                            qq, k, v, lambda lo, hi: allowed[None, :, lo:hi],
                            key_pos, t[-1], scale)

                def blocked(a):
                    return a.reshape((S // qb, qb) + a.shape[1:])

                o = jax.lax.map(block, (blocked(q), blocked(q_i),
                                        blocked(w_i), blocked(pos)))
                return (o.reshape(S, nh * self.v_dim), (kv_rows, ik_rows),
                        mod._no_counts())

            x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
            h, keeps, counts = self._blocks(
                params, x, pos,
                jnp.arange(S) < (length if counted is None else counted),
                attend)
            kvs, iks = map(list, zip(*keeps))
            return h, kvs, iks, counts

    return _lm(Oracle)[0]


_BLOCKS = ("prefill_query_blocks", "prefill_query_blocks_live")


def _small_blocks(monkeypatch, key_chunk=8):
    """Query blocks of 4 and key chunks of 8, so that a toy bucket of 32
    holds 8 blocks over 4 key chunks (the served 8,192 bucket: 32 over 2)."""
    from analytics_zoo_tpu.models import latent_moe_lm
    monkeypatch.setattr(latent_moe_lm, "_QUERY_BLOCK", 4)
    monkeypatch.setattr(latent_moe_lm, "_KEY_CHUNK", key_chunk)


def _shared_draw(lm, params):
    """A pool that holds the 8-token prefix of a document in blocks 1-2, and
    the arguments of a suffix-only prefill over it: two rows in a bucket of
    32, 13 and 6 real tokens at positions 8.."""
    doc = np.random.default_rng(36).integers(1, 97, (1, 32)).astype(np.int32)
    filled, _, _ = _prefilled(lm, params, doc[:, :8], np.array([8]),
                              ntab=20)
    suffix = np.zeros((2, 32), np.int32)
    suffix[0, :13], suffix[1, :6] = doc[0, 8:21], doc[0, 8:14]
    lens, base = np.array([13, 6], np.int32), np.array([8, 8], np.int32)
    ptab = np.array([[1, 2], [1, 2]], np.int32)
    dest = np.zeros((2, 8), np.int32)
    dest[0, :4], dest[1, :2] = [3, 4, 5, 6], [7, 8]
    return filled, (suffix, lens, base, ptab, dest, np.arange(2))


@pytest.mark.parametrize("path", ["paged", "shared"])
def test_a_skipped_block_changes_no_real_position_bit_for_bit(monkeypatch,
                                                              path):
    """Rows of 13, 6 and 18 tokens in a bucket of 32 (8 query blocks, 3-6 of
    them past the row): at every REAL position the final hidden states, the
    ``kv`` / ``ik`` rows the pool received and the last position's logits
    are the same BITS as with every block attended, through ``prefill_paged``
    and through ``prefill_shared_paged`` at ``base`` 8: a real query's block
    runs the code it ran, and no real position reads a padding query's
    output."""
    import jax
    from analytics_zoo_tpu.ops import paged_attention as paged
    _small_blocks(monkeypatch)
    lm, params, _ = _lm()
    oracle = _unskipped_oracle()
    if path == "paged":
        prompt = np.random.default_rng(36).integers(1, 97, (3, 32)) \
            .astype(np.int32)
        lens = np.array([13, 6, 18], np.int32)
        base = np.zeros((3,), np.int32)
        dest = np.where(np.arange(8)[None] * 4 < lens[:, None],
                        1 + np.arange(24).reshape(3, 8), 0)
        state = jax.device_put(lm.init_paged_pools(1 + 24, 4, 3))
        args = (prompt, lens, dest, np.arange(3))
        prefixes = [None] * 3

        def program(model):
            return functools.partial(model.prefill_paged, block_len=4)
    else:
        state, args = _shared_draw(lm, params)
        prompt, lens, base, ptab, dest = args[:5]
        prefixes = [tuple([paged.latent_gather(p, ptab[r:r + 1])[0]
                           for p in state[leaf]] for leaf in ("kv", "ik"))
                    for r in range(2)]

        def program(model):
            return functools.partial(model.prefill_shared_paged, block_len=4)

    got, logits = jax.jit(program(lm))(params, state, *args)
    want, ologits = jax.jit(program(oracle))(params, state, *args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ologits))
    for r, n in enumerate(lens):
        for leaf in ("kv", "ik"):
            for a, b in zip(got[leaf], want[leaf]):
                rows = [np.asarray(x)[dest[r, :-(-n // 4)]].reshape(
                    -1, x.shape[-1])[:n] for x in (a, b)]
                assert rows[0].shape[0] == n            # each one written
                assert np.abs(rows[0]).max(-1).min() > 0
                np.testing.assert_array_equal(*rows)
        hs = [np.asarray(jax.jit(
            lambda p, ids, m=m, r=r: m._forward_row(
                p, ids, lens[r], base[r], prefixes[r])[0])(
                    params, prompt[r]))[:n] for m in (lm, oracle)]
        np.testing.assert_array_equal(*hs)
    # the skip is taken: past the last live block the two DO differ
    assert np.abs(hs[0]).max() > 0
    after = [np.asarray(jax.jit(
        lambda p, ids, m=m: m._forward_row(p, ids, 6, base[1],
                                           prefixes[1])[0])(
            params, prompt[1]))[8:] for m in (lm, oracle)]
    assert np.abs(after[0] - after[1]).max() > 1e-3


@pytest.mark.parametrize("case", ["rows", "padding_row", "shared", "call"])
def test_the_query_block_counters_count_blocks_asked_for_and_run(monkeypatch,
                                                                 case):
    """A row of ``n`` tokens in a bucket of ``S`` adds ``layers x S / 4`` to
    ``prefill_query_blocks`` and ``layers x ceil(n / 4)`` to
    ``prefill_query_blocks_live`` (through both prefill programs); a batch's
    padding row (length 1, every block to the trash) adds ONE live block a
    layer; teacher forcing (``call``: ``length = S``) runs every block."""
    import jax
    from analytics_zoo_tpu.models.latent_moe_lm import COUNTERS
    _small_blocks(monkeypatch)
    lm, params, _ = _lm()
    L = lm.n_layers
    if case == "call":
        ids = np.random.default_rng(37).integers(1, 97, 32).astype(np.int32)
        counts = np.asarray(jax.jit(
            lambda p, x: lm._forward_row(p, x, x.shape[0])[3])(params, ids))
        got = dict(zip(COUNTERS, counts))
        assert (got[_BLOCKS[0]], got[_BLOCKS[1]]) == (L * 8, L * 8)
        return
    if case == "shared":
        state, args = _shared_draw(lm, params)
        lens = args[1]
        before = lm.paged_counters(state)
        state, _ = jax.jit(functools.partial(
            lm.prefill_shared_paged, block_len=4))(params, state, *args)
    else:
        lens = np.array([13, 32, 1] if case == "rows" else [18, 1], np.int32)
        prompt = np.random.default_rng(37).integers(
            1, 97, (len(lens), 32)).astype(np.int32)
        dest = np.where(np.arange(8)[None] * 4 < lens[:, None],
                        1 + np.arange(8 * len(lens)).reshape(-1, 8), 0)
        if case == "padding_row":
            dest[1] = 0
        state = jax.device_put(lm.init_paged_pools(1 + dest.size, 4,
                                                   len(lens)))
        before = lm.paged_counters(state)
        state, _ = jax.jit(functools.partial(lm.prefill_paged, block_len=4))(
            params, state, prompt, lens, dest, np.arange(len(lens)))
    after = lm.paged_counters(state)
    assert after[_BLOCKS[0]] - before[_BLOCKS[0]] == L * 8 * len(lens)
    assert after[_BLOCKS[1]] - before[_BLOCKS[1]] \
        == L * sum(-(-int(n) // 4) for n in lens)
    if case == "padding_row":
        # ... and it stays out of the expert layer's counts, as before
        assert after["moe_pairs"] == (L - 1) * 18 * 3


def test_the_lowered_prefill_keeps_the_skip_a_conditional(monkeypatch):
    """In the prefill program every layer's query-block loop (a ``scan`` of
    bucket / 4 steps inside the rows' ``scan``) holds ONE ``cond`` and no
    matmul beside it; the ``cond``'s live branch holds the block's three
    stages and the other none; and the StableHLO text carries one ``case`` a
    layer (one key chunk here, so ``attend_chunks`` adds none).  A later edit
    that batches the block (a ``vmap`` turns ``cond`` into ``select``: every
    padding block computed again) fails here, not in a benchmark."""
    import re

    import jax
    _small_blocks(monkeypatch, key_chunk=4096)
    lm, params, _ = _lm()
    state = jax.device_put(lm.init_paged_pools(1 + 24, 4, 3))
    fn = functools.partial(lm.prefill_paged, block_len=4)
    args = (params, state, np.zeros((3, 32), np.int32),
            np.array([13, 6, 1], np.int32), np.zeros((3, 8), np.int32),
            np.arange(3))
    loops = []

    def stages(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(re.findall(r"zoo\.lm\.(\w+)",
                                      str(eqn.source_info.name_stack))[-1])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                stages(sub, out)
        return out

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" and eqn.params["length"] == 8:
                loops.append(eqn.params["jaxpr"].jaxpr)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(loops) == lm.n_layers
    for body in loops:
        names = [e.primitive.name for e in body.eqns]
        assert names.count("cond") == 1 and "dot_general" not in names
        cond = body.eqns[names.index("cond")]
        held = sorted(sorted(set(stages(b.jaxpr, [])))
                      for b in cond.params["branches"])
        assert held == [[], ["dsa_index", "mla"]]
        assert "select_n" not in names
    text = jax.jit(fn).lower(*args).as_text()
    assert text.count("stablehlo.case") == lm.n_layers


def test_a_decode_call_leaves_the_query_block_counters():
    """Three rows were prefilled (one block each at the toy bucket); two
    decode steps later the two counters read what they read."""
    import jax
    lm, params, state, tables, pos, tok = _decode_rows()
    before = lm.paged_counters(state)
    assert before[_BLOCKS[0]] == before[_BLOCKS[1]] == 3 * lm.n_layers
    step = jax.jit(functools.partial(lm.decode_paged, block_len=4))
    for _ in range(2):
        logits, state = step(params, state, tables, pos, tok)
        tok, pos = np.asarray(logits).argmax(-1).astype(np.int32), pos + 1
    after = lm.paged_counters(state)
    assert {n: after[n] for n in _BLOCKS} == {n: before[n] for n in _BLOCKS}
    assert after["dsa_keys_context"] > before["dsa_keys_context"]


# -- (d), (e) the expert layer -------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Over both shares of the 8 experts, the routed parts plus the shared
    expert counted ONCE equal the uncut reference layer."""
    import jax
    import jax.numpy as jnp
    import reference_latent_moe as ref
    lm, params, _ = _lm(n_routed_experts=8, published={}, deployment={})
    blk = params["blocks"][1]
    cfg_all = dict(CFG, n_routed_experts=8, published={}, deployment={})
    h = jnp.asarray(np.random.default_rng(4).normal(size=(40, 32)),
                    jnp.float32)
    valid = jnp.ones((40,), bool)
    shared = lm._swiglu(h, blk["s_gate"], blk["s_up"], blk["s_down"])
    total, pairs = shared, 0
    for chip in range(2):
        part, _, _ = _lm(deployment={"chip": chip})
        mine = dict(blk, **{n: blk[n][4 * chip:4 * chip + 4]
                            for n in ("w_gate", "w_up", "w_down")})
        y, counts = jax.jit(part._moe, static_argnums=3)(mine, h, valid,
                                                         False)
        total = total + (y - shared)
        pairs += int(counts[1])
    assert pairs == 40 * 3            # every pair landed on exactly one share
    want, _ = jax.jit(lambda b_, h_: ref.routed_ffn(
        b_, cfg_all, h_, lambda x: x))(blk, h)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("slab", [8, 2048])
def test_no_pair_is_dropped_when_every_token_picks_one_expert(monkeypatch,
                                                              slab):
    """A selection bias that sends every token to held expert 5 (and to
    two experts this share does not hold): 40 pairs in one group, more than
    a slab of 8 takes at once, none lost."""
    import jax
    import jax.numpy as jnp
    import reference_latent_moe as ref
    from analytics_zoo_tpu.models import latent_moe_lm
    monkeypatch.setattr(latent_moe_lm, "_PAIR_SLAB", slab)
    lm, params, cfg = _lm()
    bias = np.zeros((8,), np.float32)
    bias[[5, 0, 1]] = 10.0, 9.0, 8.0
    blk = dict(params["blocks"][1], e_bias=jnp.asarray(bias))
    h = jnp.asarray(np.random.default_rng(6).normal(size=(40, 32)),
                    jnp.float32)
    y, counts = jax.jit(lambda b_, h_: lm._moe(
        b_, h_, jnp.ones((40,), bool), True))(blk, h)
    assert [int(c) for c in counts[:5]] == [120, 40, 40, 1, 1]
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(jax.jit(lambda b_, h_: ref.routed_ffn(
            b_, cfg, h_, lambda x: x)[0])(blk, h)),
        atol=LOGIT_TOL, rtol=0)


# -- the tolerance -------------------------------------------------------------

def test_a_lower_precision_fails_the_tolerance():
    """The same float32-configured model computed in bfloat16 (the
    reference's ``round_to``: every matmul operand through bfloat16) leaves
    ``LOGIT_TOL`` by two orders, so the comparisons above would catch it;
    and the benchmark's check, given the tokens that forward serves, reads
    margins that are small and not zero."""
    import jax.numpy as jnp
    import reference_latent_moe as ref
    _, params, cfg = _lm()
    ids = np.random.default_rng(7).integers(1, 97, 38).astype(np.int32)
    exact = _ref_logits(params, cfg, ids)
    low = _ref_logits(params, cfg, ids, round_to=jnp.bfloat16)
    assert np.abs(low - exact).max() > 100 * LOGIT_TOL
    seq = list(ids[:8])
    for _ in range(6):             # the reference's own greedy continuation
        seq.append(int(_ref_logits(params, cfg, seq,
                                   [len(seq) - 1]).argmax()))
    doc = ref.check_served(params, cfg, [
        {"prompt": seq[:8], "tokens": seq[8:]}], 48)
    assert doc["ok"] and doc["max_logit_margin"] == 0.0
    wrong = [(t + 1) % 97 for t in seq[8:]]
    assert not ref.check_served(params, cfg, [
        {"prompt": seq[:8], "tokens": wrong}], 48)["ok"]
