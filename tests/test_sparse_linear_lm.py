"""``models/sparse_linear_lm.SparseLinearLM`` (PR 35) against its plain
reference (``benchmark/reference_sparse_linear.py``, which calls no model
code), at tiny widths in FLOAT32 on the CPU, with the REAL structure: 2 key
heads under 16-head groups, the published pattern's pieces (``m L L L`` then
``m m`` then a second linear run), and the selection's sizes scaled down so
that contexts cross ``dense_len`` and blocks are dropped (kernel 4, stride 2,
block 4, topk 4 = block 0 + 2 local blocks + 1 scored, ``dense_len`` 16).

TOLERANCE.  Both sides compute in float32 (the reference at ``highest``), so
they differ by summation order only: logits of magnitude ~2 agree to ~2e-6
(measured).  ``LOGIT_TOL`` = 2e-4 leaves 100 x of room and is far under what
bfloat16 operands read (``test_a_lower_precision_fails_the_tolerance``).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

LOGIT_TOL = 2e-4
S, L = "minicpm4", "lightning-attn"
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=4, topk=4,
              init_blocks=1, window_size=8, dense_len=16)
CFG = dict(
    vocab_size=97, hidden_size=64, num_hidden_layers=8,
    mixer_types=[S, L, L, L, S, S, L, L], intermediate_size=96,
    num_attention_heads=32, num_key_value_heads=2, head_dim=8,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=8, scale_emb=12,
    scale_depth=1.4, dim_model_base=16, rope_theta=10000, rms_norm_eps=1e-6,
    max_position_embeddings=128, sparse_config=SPARSE,
    published={"num_hidden_layers": 32})
N_SPARSE, N_LINEAR, G = 3, 5, 2

_BUILT = {}


def _lm():
    """``(model, weights)``, built once; the chunk sizes cut to the toy's so
    that every loop of the prefill runs more than once."""
    import jax
    from analytics_zoo_tpu.models import sparse_linear_lm as M
    if not _BUILT:
        M._POS_CHUNK, M._QUERY_BLOCK, M._KEY_CHUNK, M._LIN_CHUNK = 16, 8, 16, 4
        lm = M.SparseLinearLM.from_config(CFG, dtype="float32",
                                          initializer_range=0.3)
        _BUILT["lm"] = lm, jax.jit(lm.build)(jax.random.PRNGKey(0))
    return _BUILT["lm"]


def _ref_logits(params, ids, rows=None, **kw):
    """The reference over ``ids`` right-padded to ONE length (every layer is
    causal, so the padding is harmless): its layers compile once."""
    import reference_sparse_linear as ref
    padded = np.zeros((64,), np.int32)
    padded[:len(ids)] = ids
    return ref.logits(params, CFG, padded,
                      np.arange(len(ids)) if rows is None else rows, **kw)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).astype(np.int32)


# -- (a) the full forward ------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_call_equals_the_reference(seed):
    """``call`` over 48 positions (chunks of 16, query blocks of 8, key chunks
    of 16, linear chunks of 4) against the reference position by position;
    the selection drops blocks: a late query keeps 4 of its 12."""
    import jax
    lm, params = _lm()
    ids = _ids(seed, 48)
    got = np.asarray(jax.jit(lm.call)(params, ids[None]))[0]
    probe = []
    want = _ref_logits(params, ids, probe=probe)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert len(probe) == N_SPARSE
    kept = probe[0][:, :48]                       # (key heads, positions)
    assert (kept[:, 15] == 4).all()               # context 16: dense, 4 blocks
    assert (kept[:, 16:] == 4).all() and kept.shape[0] == G   # 4 of 5 .. 12


def test_a_lower_precision_fails_the_tolerance():
    import jax.numpy as jnp
    _, params = _lm()
    ids = _ids(1, 48)
    exact = _ref_logits(params, ids)
    assert np.abs(_ref_logits(params, ids, round_to=jnp.bfloat16)
                  - exact).max() > 20 * LOGIT_TOL
    assert np.abs(_ref_logits(params, ids, state_dtype=jnp.bfloat16)
                  - exact).max() > 5 * LOGIT_TOL


# -- (b) prefill, then decode through the paged state --------------------------

@pytest.mark.parametrize("lengths", [(13, 30), (16, 7), (32, 21)],
                         ids=["crosses_dense_len", "at_dense_len", "ragged"])
def test_prefill_then_decode_equal_the_references_forward(lengths):
    """``prefill_paged`` then 16 ``decode_paged`` steps a row, logits against
    the reference's forward over the whole sequence: across block boundaries
    (every 4th position), compressed-key windows that span two blocks (every
    other window), the step at which a context passes ``dense_len`` 16, and
    with one row's padding beside the other's real positions."""
    import jax
    lm, params = _lm()
    A, bl, ntab, P = 2, 4, 16, 32
    seqs = np.stack([_ids(11, 48), _ids(12, 48)])
    lens = np.asarray(lengths, np.int32)
    want = [_ref_logits(params, seqs[a]) for a in range(A)]
    state = jax.device_put(lm.init_paged_pools(1 + A * ntab, bl, A))
    tables = 1 + np.arange(A * ntab, dtype=np.int32).reshape(A, ntab)
    prompt = np.where(np.arange(P)[None] < lens[:, None], seqs[:, :P], 0)
    state, logits0 = jax.jit(lambda *a: lm.prefill_paged(*a, block_len=bl))(
        params, state, prompt, lens, tables[:, :P // bl],
        np.arange(A, dtype=np.int32))
    for a in range(A):
        np.testing.assert_allclose(np.asarray(logits0)[a],
                                   want[a][lens[a] - 1], atol=LOGIT_TOL,
                                   rtol=0)
    step = jax.jit(lambda *a: lm.decode_paged(*a, block_len=bl))
    pos = lens.copy()
    for _ in range(16):
        logits, state = step(params, state, tables, pos,
                             seqs[np.arange(A), pos])
        for a in range(A):
            np.testing.assert_allclose(np.asarray(logits)[a], want[a][pos[a]],
                                       atol=LOGIT_TOL, rtol=0)
        pos = pos + 1
    c = lm.paged_counters(state)
    assert c["sparse_rows"] == 16 * A * N_SPARSE
    assert c["lin_state_updates"] == 16 * A * N_LINEAR
    assert c["prefill_positions_linear"] == int(lens.sum()) * N_LINEAR
    dense = sum(int(n + i + 1 <= 16) for n in lens for i in range(16))
    assert c["sparse_rows_dense"] == dense * N_SPARSE


def test_a_padding_row_and_an_idle_slot_change_nothing():
    """A batch's padding row (slot = the drop sentinel, blocks = trash) is
    skipped whole; an idle slot's decode step (table all trash) leaves every
    state of a real slot and its own recurrent state as they were."""
    import jax
    lm, params = _lm()
    A, bl, ntab = 2, 4, 16
    state = jax.device_put(lm.init_paged_pools(1 + A * ntab, bl, A))
    tables = np.zeros((A, ntab), np.int32)
    tables[0] = 1 + np.arange(ntab)
    ids = _ids(3, 32)
    prompt = np.stack([ids, ids])
    dest = np.zeros((2, 8), np.int32)
    dest[0] = tables[0, :8]
    state, _ = jax.jit(lambda *a: lm.prefill_paged(*a, block_len=bl))(
        params, state, prompt, np.asarray([32, 32], np.int32), dest,
        np.asarray([0, A], np.int32))
    assert lm.paged_counters(state)["prefill_positions_linear"] \
        == 32 * N_LINEAR
    lin = np.asarray(state["lin"])
    assert np.abs(lin[:, 0]).max() > 0 and not lin[:, 1].any()
    _, after = jax.jit(lambda *a: lm.decode_paged(*a, block_len=bl))(
        params, state, tables, np.asarray([32, 5], np.int32),
        np.asarray([7, 9], np.int32))
    assert not np.asarray(after["lin"])[:, 1].any()
    assert lm.paged_counters(after)["sparse_rows"] == N_SPARSE


def test_the_recurrent_state_keeps_the_type_the_configuration_states():
    """``correct`` cannot see the state's precision (with a seed's weights a
    bfloat16 state serves the float32 tokens: PERF.md section 7), so the type
    is pinned HERE: the benchmark's configuration states a float32 state beside
    bfloat16 weights, and that is what every paged program hands back."""
    import json
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.sparse_linear_lm import SparseLinearLM
    stated = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "minicpm-sala.json")))["assumed"]["served_dtype"]
    assert "bfloat16 weights" in stated and "float32 recurrent state" in stated
    lm = SparseLinearLM.from_config(CFG)            # bfloat16, as served
    params = jax.eval_shape(lm.build, jax.random.PRNGKey(0))
    assert params["embed"].dtype == jnp.bfloat16
    A, bl, ntab, P = 2, 4, 16, 32
    state = lm.init_paged_pools(1 + A * ntab, bl, A)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    filled, _ = jax.eval_shape(
        lambda *a: lm.prefill_paged(*a, block_len=bl), params, state,
        i32(A, P), i32(A), i32(A, P // bl), i32(A))
    _, stepped = jax.eval_shape(
        lambda *a: lm.decode_paged(*a, block_len=bl), params, state,
        i32(A, ntab), i32(A), i32(A))
    for tree in (state, filled, stepped):
        assert tree["lin"].dtype == jnp.float32
        assert tree["lin"].shape == (N_LINEAR, A, 4, 8, 8)
        assert tree["k"][0].dtype == jnp.bfloat16


# -- (c) the two forms of the recurrence ---------------------------------------

@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_equals_step_equals_the_recurrence(chunk):
    """``ops/linear_attention.chunked`` == ``step`` iterated == the
    recurrence written out in NumPy, with ragged ``lengths``: padding neither
    enters the state nor decays it."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import linear_attention as la
    g = np.random.default_rng(4)
    B, T, H, d = 3, 32, 4, 8
    q, k, v = (g.normal(size=(B, T, H, d)).astype(np.float32)
               for _ in range(3))
    decay = np.exp(-2.0 ** (-8.0 * np.arange(1, H + 1) / H)).astype(
        np.float32)
    S0 = g.normal(size=(B, H, d, d)).astype(np.float32)
    lengths = np.asarray([32, 13, 0], np.int32)
    # the recurrence, float64
    want_o = np.zeros((B, T, H, d))
    want_S = S0.astype(np.float64)
    for b in range(B):
        S = S0[b].astype(np.float64)
        for t in range(lengths[b]):
            S = decay[:, None, None] * S + k[b, t][:, :, None] \
                * v[b, t][:, None, :]
            want_o[b, t] = np.einsum("hd,hde->he", q[b, t], S)
        want_S[b] = S
    o, Sn = la.chunked(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       jnp.asarray(decay), jnp.asarray(S0), lengths,
                       chunk=chunk)
    real = np.arange(T)[None, :, None, None] < lengths[:, None, None, None]
    np.testing.assert_allclose(np.where(real, np.asarray(o), 0.0), want_o,
                               atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(Sn), want_S, atol=2e-4, rtol=1e-5)
    assert np.isfinite(np.asarray(o)).all()
    S = jnp.asarray(S0[:1])
    for t in range(T):
        o_t, S = la.step(jnp.asarray(q[:1, t]), jnp.asarray(k[:1, t]),
                         jnp.asarray(v[:1, t]), jnp.asarray(decay), S)
        np.testing.assert_allclose(np.asarray(o_t)[0], want_o[0, t],
                                   atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S)[0], want_S[0], atol=2e-4,
                               rtol=1e-5)


# -- (d), (e), (f) through the unmodified scheduler ----------------------------

def _batcher(lm, params, **kw):
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams)
    im = InferenceModel().do_load_model(lm, params, {})
    geo = dict(max_active_slots=2, max_prompt_len=32, max_tokens=13,
               prefill_buckets=[16, 32], paged=True, block_len=4,
               decode_quantum=2, prefix_cache=False, stream_interval=0)
    geo.update(kw)
    return ContinuousBatcher(im, GenerationParams(**geo))


def _drive(b, reqs):
    from analytics_zoo_tpu.serving.generate import GenRequest
    for rid, prompt, budget in reqs:
        assert b.submit(GenRequest(rid, prompt, max_tokens=budget))
    done = {}
    for _ in range(2000):
        for ev in b.step():
            assert ev.kind not in ("shed", "quarantine"), ev.error
            if ev.kind == "finish":
                done[ev.rid] = list(ev.tokens)
        if len(done) == len(reqs):
            return [done[rid] for rid, _, _ in reqs]
    raise AssertionError(f"stalled: {len(done)}/{len(reqs)}")


# budgets b with (b - 1) % decode_quantum == 0: no row-step is wasted
REQS = [("r0", 5, 13), ("r1", 14, 7), ("r2", 30, 9), ("r3", 21, 11)]


@pytest.fixture(scope="module")
def served():
    from analytics_zoo_tpu.inference import aot
    lm, params = _lm()
    b = _batcher(lm, params)
    doc = b.warm()
    assert doc["failed"] == 0, doc["errors"]
    reqs = [(rid, _ids(20 + i, n), budget)
            for i, (rid, n, budget) in enumerate(REQS)]
    c0 = aot.COMPILE_STATS.snapshot()
    tokens = _drive(b, reqs)
    assert aot.COMPILE_STATS.snapshot()["compile_requests"] \
        == c0["compile_requests"], "traffic compiled after the warm-up"
    return b, lm, params, reqs, tokens


@pytest.mark.parametrize("i", range(4), ids=[r[0] for r in REQS])
def test_served_tokens_are_the_references_best(served, i):
    """Four requests (contexts 5-39 against ``dense_len`` 16) on two slots of
    an unmodified ``ContinuousBatcher``: every served token is the argmax of
    the reference's teacher-forced forward of prompt + served tokens."""
    _, _, params, reqs, tokens = served
    prompt = reqs[i][1]
    ids = np.concatenate([prompt, np.asarray(tokens[i], np.int32)])
    want = _ref_logits(params, ids, np.arange(len(prompt) - 1, len(ids) - 1))
    assert list(want.argmax(-1)) == tokens[i]


def test_the_counters_total_what_the_requests_needed(served):
    b, lm, _, reqs, tokens = served
    c = b.stats()
    got = {k[len("model."):]: v for k, v in c.items()
           if k.startswith("model.")}
    rows = dense = kept = context = 0
    for (_, prompt, _), toks in zip(reqs, tokens):
        for j in range(len(toks) - 1):            # decode steps of the row
            ctx = len(prompt) + j + 1
            blocks = -(-ctx // 4)
            rows += 1
            dense += ctx <= 16
            kept += blocks if ctx <= 16 else min(4, blocks)
            context += blocks
    assert got["sparse_rows"] == rows * N_SPARSE
    assert got["sparse_rows_dense"] == dense * N_SPARSE
    assert got["sparse_blocks_kept"] == kept * N_SPARSE * G
    assert got["sparse_blocks_context"] == context * N_SPARSE * G
    assert got["lin_state_updates"] == rows * N_LINEAR
    assert got["prefill_positions_linear"] \
        == sum(len(p) for _, p, _ in reqs) * N_LINEAR
    lane = b._lanes[0]
    assert set(lane.state) == {"k", "v", "ck", "lin", "counters"}
    assert b._record_of[("pdecode", lane.bucket)]["alias_bytes"] \
        == lane.state_nbytes
    doc = b.state_bytes_doc()
    n_blocks = b._pool.n_blocks + 1
    assert doc["paged_pool"] == N_SPARSE * n_blocks * (2 * 4 + 2) * 2 * 8 * 4
    assert doc["lanes"] == N_LINEAR * 2 * 4 * 8 * 8 * 4 + 6 * 2 * 4


def test_a_slot_reused_by_a_shorter_request_serves_a_fresh_batchers_tokens(
        served):
    """One slot: a long request, then a short one in the same slot (recurrent
    state, compressed keys and blocks all overwritten), against a batcher
    that has served nothing."""
    _, lm, params, _, _ = served
    long_req = ("long", _ids(31, 30), 13)
    short_req = ("short", _ids(32, 6), 13)
    used = _batcher(lm, params, max_active_slots=1)
    _drive(used, [long_req])
    fresh = _batcher(lm, params, max_active_slots=1)
    assert _drive(used, [short_req]) == _drive(fresh, [short_req])


def test_prefix_cache_is_refused_at_start(served):
    _, lm, params, _, _ = served
    with pytest.raises(ValueError, match="prefix_cache"):
        _batcher(lm, params, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="shared prefix"):
        lm.prefill_shared_paged(params, {}, None, None, None, None, None,
                                None, block_len=4)
    with pytest.raises(ValueError, match="block_size"):
        lm.init_paged_pools(9, 8, 2)
