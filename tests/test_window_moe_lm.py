"""``models/window_moe_lm.WindowMoELM`` against its plain reference
(``benchmark/reference_window_moe.py``, which calls no model code), at tiny
widths in FLOAT32 on the CPU, with the REAL structure: one period of the
published pattern (a full NoPE layer, then three window layers with rotary),
2 key heads under 4-head groups, 8 routed experts top-2 whose router reads the
layer's input, and the window (16) short enough that contexts pass it twice.
The program's chunk sizes are cut to the toy's, so that every loop runs more
than once: positions 16 a chunk, queries 8 a block, window keys 4 a chunk.

TOLERANCE.  Both sides compute in float32 (the reference at ``highest``), so
they differ by summation order only (measured ~1e-6 on logits of magnitude
~3).  ``LOGIT_TOL`` = 2e-4 leaves 100 x of room and is far under what
bfloat16 operands read (``test_a_lower_precision_fails_the_tolerance``).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

LOGIT_TOL = 2e-4
WINDOW = 16
CFG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], sliding_window_size=WINDOW,
    rope_theta=1.5e6, rms_norm_eps=1e-6, max_position_embeddings=64)
N_FULL, N_WINDOW, TOP_K = 1, 3, 2

_BUILT = {}


def _lm():
    """``(model, weights)``, built once; the chunk sizes cut to the toy's."""
    import jax
    from analytics_zoo_tpu.models import window_moe_lm as M
    if not _BUILT:
        M._POS_CHUNK, M._QUERY_BLOCK, M._KEY_CHUNK = 16, 8, 16
        M._WINDOW_CHUNK, M._DECODE_CHUNK, M._PAIR_SLAB = 4, 8, 16
        lm = M.WindowMoELM.from_config(CFG, dtype="float32",
                                       initializer_range=0.3)
        _BUILT["lm"] = lm, jax.jit(lm.build)(jax.random.PRNGKey(0))
    return _BUILT["lm"]


def _ref_logits(params, ids, rows=None, **kw):
    """The reference over ``ids`` right-padded to ONE length (every layer is
    causal, so the padding is harmless): its layers compile once."""
    import reference_window_moe as ref
    padded = np.zeros((64,), np.int32)
    padded[:len(ids)] = ids
    return ref.logits(params, CFG, padded,
                      np.arange(len(ids)) if rows is None else rows, **kw)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _prefill(lm, params, state, prompt, lens, dest, slots, bl):
    import jax
    return jax.jit(lambda *a: lm.prefill_paged(*a, block_len=bl))(
        params, state, prompt, np.asarray(lens, np.int32), dest,
        np.asarray(slots, np.int32))


def _decode(lm, bl, impl=None):
    import jax
    return jax.jit(lambda *a: lm.decode_paged(*a, block_len=bl,
                                              impl=impl))


# -- (a) the full forward ------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_call_equals_the_reference(seed):
    """``call`` over 64 positions (4 windows) against the reference's forward:
    explicit window masks, dense experts weighted where chosen."""
    import jax
    lm, params = _lm()
    ids = _ids(seed, 64)
    got = np.asarray(jax.jit(lm.call)(params, ids[None]))[0]
    np.testing.assert_allclose(got, _ref_logits(params, ids), atol=LOGIT_TOL,
                               rtol=0)


def test_a_lower_precision_fails_the_tolerance():
    import jax.numpy as jnp
    _, params = _lm()
    ids = _ids(1, 64)
    exact = _ref_logits(params, ids)
    assert np.abs(_ref_logits(params, ids, round_to=jnp.bfloat16)
                  - exact).max() > 20 * LOGIT_TOL


@pytest.mark.parametrize("layouts", [([0, 1, 1], [0, 1, 1, 1]),
                                     ([0, 1, 1, 1], [0, 1, 0, 1]),
                                     ([0, 2, 1, 1], [0, 2, 1, 1])],
                         ids=["short", "disagree", "not_0_or_1"])
def test_layouts_the_class_cannot_serve_are_refused(layouts):
    from analytics_zoo_tpu.models.window_moe_lm import WindowMoELM
    with pytest.raises(ValueError, match="layout"):
        WindowMoELM.from_config(dict(CFG, rope_layout=layouts[0],
                                     sliding_window_layout=layouts[1]))


# -- (b) prefill, then decode through the paged state --------------------------

@pytest.mark.parametrize("lengths", [(10, 16), (30, 7), (32, 21)],
                         ids=["below_2w", "at_2w", "past_2w"])
def test_prefill_then_decode_equal_the_references_forward(lengths):
    """``prefill_paged`` then 16 ``decode_paged`` steps a row, logits against
    the reference's forward over the whole sequence: contexts below, at and
    past 2 x the window (the ring's fill and its wrap), across block
    boundaries (every 4th position), a row's padding beside the other's
    real positions, and a 32-position prompt whose window layers' blocks
    read a slice of the keys."""
    lm, params = _lm()
    A, bl, ntab, P = 2, 4, 16, 32
    seqs = np.stack([_ids(11, 64), _ids(12, 64)])
    lens = np.asarray(lengths, np.int32)
    want = [_ref_logits(params, seqs[a]) for a in range(A)]
    import jax
    state = jax.device_put(lm.init_paged_pools(1 + A * ntab, bl, A))
    tables = 1 + np.arange(A * ntab, dtype=np.int32).reshape(A, ntab)
    prompt = np.where(np.arange(P)[None] < lens[:, None], seqs[:, :P], 0)
    state, logits0 = _prefill(lm, params, state, prompt, lens,
                              tables[:, :P // bl], range(A), bl)
    for a in range(A):
        np.testing.assert_allclose(np.asarray(logits0)[a],
                                   want[a][lens[a] - 1], atol=LOGIT_TOL,
                                   rtol=0)
    step = _decode(lm, bl)
    pos = lens.copy()
    for _ in range(16):
        logits, state = step(params, state, tables, pos,
                             seqs[np.arange(A), pos])
        for a in range(A):
            np.testing.assert_allclose(np.asarray(logits)[a], want[a][pos[a]],
                                       atol=LOGIT_TOL, rtol=0)
        pos = pos + 1
    c = lm.paged_counters(state)
    ctx = [n + i + 1 for n in lens for i in range(16)]
    assert c["window_keys_context"] == sum(ctx) * N_WINDOW
    assert c["window_keys_attended"] == sum(min(n, WINDOW) for n in ctx) \
        * N_WINDOW
    assert c["moe_layer_steps"] == 16 * 4
    assert c["moe_pairs"] == (int(lens.sum()) + 16 * A) * TOP_K * 4


def test_a_slot_reused_after_a_longer_request_gives_a_fresh_slots_logits():
    """Slot 0 serves a 40-token context first; then a 6-token prompt in the
    same slot (its ring holds the longer request's rows past the short one's
    positions) decodes 12 steps: the logits are a fresh state's."""
    _slot_reused(impl=None)


def test_a_slot_reused_through_the_kernels_gives_a_fresh_slots_logits(
        folds_of_four):
    """The same with ``impl="interpret"``: the decode layers' routed experts
    through ``ops/expert_matmul``'s kernel, the full layers' read through
    the grouped-page kernel."""
    _slot_reused(impl="interpret")


def _slot_reused(impl):
    import jax
    lm, params = _lm()
    A, bl, ntab, P = 1, 4, 16, 32
    tables = 1 + np.arange(ntab, dtype=np.int32)[None]
    step = _decode(lm, bl, impl)

    def serve(state, ids, n, steps):
        prompt = np.zeros((1, P), np.int32)
        prompt[0, :n] = ids[:n]
        state, first = _prefill(lm, params, state, prompt, [n],
                                tables[:, :P // bl], [0], bl)
        out, pos = [np.asarray(first)[0]], np.asarray([n], np.int32)
        for _ in range(steps):
            logits, state = step(params, state, tables, pos, ids[pos])
            out.append(np.asarray(logits)[0])
            pos = pos + 1
        return state, np.stack(out)

    used, _ = serve(jax.device_put(lm.init_paged_pools(1 + ntab, bl, A)),
                    _ids(31, 64), 30, 10)
    short = _ids(32, 64)
    _, again = serve(used, short, 6, 12)
    _, fresh = serve(jax.device_put(lm.init_paged_pools(1 + ntab, bl, A)),
                     short, 6, 12)
    np.testing.assert_array_equal(again, fresh)


def test_a_padding_row_and_an_idle_slot_change_nothing():
    """A batch's padding row (blocks all trash, slot = the drop sentinel) is
    skipped whole; an idle slot's decode step (table all trash) leaves its
    rings as they were, and counts nothing."""
    _padding_and_idle(impl=None)


def test_an_idle_slot_through_the_kernels_changes_nothing(folds_of_four):
    """The same with ``impl="interpret"``: the idle slot's token routes no
    pair through ``ops/expert_matmul``'s kernel."""
    _padding_and_idle(impl="interpret")


def _padding_and_idle(impl):
    import jax
    lm, params = _lm()
    A, bl, ntab = 2, 4, 16
    state = jax.device_put(lm.init_paged_pools(1 + A * ntab, bl, A))
    tables = np.zeros((A, ntab), np.int32)
    tables[0] = 1 + np.arange(ntab)
    ids = _ids(3, 32)
    dest = np.zeros((2, 8), np.int32)
    dest[0] = tables[0, :8]
    state, _ = _prefill(lm, params, state, np.stack([ids, ids]), [32, 32],
                        dest, [0, A], bl)
    assert lm.paged_counters(state)["moe_pairs"] == 32 * TOP_K * 4
    rk = np.asarray(state["rk"][0])
    assert np.abs(rk[0]).max() > 0 and not rk[1].any()
    _, after = _decode(lm, bl, impl)(params, state, tables,
                                     np.asarray([32, 5], np.int32),
                                     np.asarray([7, 9], np.int32))
    for name in ("rk", "rv"):
        for before, now in zip(state[name], after[name]):
            assert not np.asarray(now)[1].any()
            np.testing.assert_array_equal(np.asarray(now)[0, :, 1:],
                                          np.asarray(before)[0, :, 1:])
    c, was = lm.paged_counters(after), lm.paged_counters(state)
    assert c["window_keys_context"] - was["window_keys_context"] \
        == 33 * N_WINDOW
    assert c["moe_pairs"] - was["moe_pairs"] == TOP_K * 4


@pytest.mark.parametrize("length", [8, 24, 64], ids=["one_block", "three_blocks",
                                                     "four_windows"])
def test_the_window_prefill_runs_fewer_chunks_than_the_causal_square(length):
    """A window layer's query block of 8 reads the 24 keys that end with it,
    in chunks of 4: from the fourth block on, 6 chunks of a causal square's
    ``b * 2 + 2``; the counters say so, a window layer and a live block at a
    time (the full layer counts nothing)."""
    import jax
    lm, params = _lm()
    bl = 4
    state = jax.device_put(lm.init_paged_pools(1 + 16, bl, 1))
    ids = _ids(5, 64)
    state, _ = _prefill(lm, params, state, ids[None], [length],
                        1 + np.arange(16, dtype=np.int32)[None], [0], bl)
    c = lm.paged_counters(state)
    blocks = -(-length // 8)
    square = sum(2 * b + 2 for b in range(blocks))
    ran = sum(min(2 * b + 2, 6) for b in range(blocks))
    assert c["prefill_window_chunks"] == square * N_WINDOW
    assert c["prefill_window_chunks_run"] == ran * N_WINDOW
    assert (ran < square) == (length > 24)


@pytest.mark.parametrize("grown", ["depth", "length"])
def test_a_prefill_program_holds_one_layers_code_and_one_key_chunks(grown):
    """Twice the layers (two periods for one), or twice the bucket (twice the
    window keys' chunks and the full layers' key chunks), adds no matmul to
    the lowered prefill program: the layers run in a scan, a block's key
    chunks in a loop.  (What each program costs a serving start is its
    lowering and compile: eight unrolled layers of unrolled chunks made the
    cell's fifteen prefill programs half of its set-up.)"""
    import jax
    from analytics_zoo_tpu.models import window_moe_lm as M
    _lm()                                  # the toy's chunk sizes

    def matmuls(n_layers, S):
        cfg = dict(CFG, num_hidden_layers=n_layers,
                   rope_layout=[0, 1, 1, 1] * (n_layers // 4),
                   sliding_window_layout=[0, 1, 1, 1] * (n_layers // 4))
        lm = M.WindowMoELM.from_config(cfg, dtype="float32")
        params = jax.eval_shape(lm.build, jax.random.PRNGKey(0))
        state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             lm.init_paged_pools(1 + S // 4, 4, 1))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32)  # noqa: E731
        text = jax.jit(lambda *a: lm.prefill_paged(*a, block_len=4)).lower(
            params, state, i32(1, S), i32(1), i32(1, S // 4), i32(1)).as_text()
        return text.count("stablehlo.dot_general")

    assert matmuls(4, 64) > 0
    assert matmuls(4, 64) == (matmuls(8, 64) if grown == "depth"
                              else matmuls(4, 128))


# -- (b2) the full layers' read: the grouped-page kernel against the XLA path --

@pytest.fixture
def folds_of_four(monkeypatch):
    """The kernel's fold cut to 4 blocks of whatever pool it is handed (at the
    cell's widths 4 blocks are 1 MB; at the toy's a fold would be the whole
    table), its trace cache cleared on both sides."""
    from analytics_zoo_tpu.ops import paged_attention as paged

    def four(n_table, block_bytes):
        return min(4, n_table)

    paged._grouped_pallas.clear_cache()
    monkeypatch.setattr(paged, "_fold_blocks", four)
    yield 4
    paged._grouped_pallas.clear_cache()


BL, NTAB = 4, 12                   # 3 folds of 4 blocks: a lane of 48


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, BL - 1, BL, BL + 1, 4 * BL + 1,
                                    NTAB * BL],
                         ids=["one", "block_less_one", "block", "block_and_one",
                              "second_fold", "lane_end"])
def test_the_grouped_page_kernel_equals_the_xla_path(length, dtype,
                                                     folds_of_four):
    """``paged.grouped_paged_attention`` (interpreted) against the chunked XLA
    gather of ``_full_decode`` on toy grouped pools whose blocks lie in a
    shuffled order: row 0 at ``length``, rows of mixed lengths beside it, and
    an idle row (first table entry the trash block) whose output is exactly
    zero.  float32 agrees to rounding; bfloat16 to the probabilities'
    rounding (2 ** -9 relative, on values of magnitude ~3)."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.models.window_moe_lm as M
    from analytics_zoo_tpu.ops import paged_attention as paged
    _lm()                                      # the toy's chunk sizes
    lm = M.WindowMoELM.from_config(CFG, dtype=dtype)
    G, J, d, A = lm.n_kv, lm.group, lm.head_dim, 5
    rng = np.random.default_rng(length)
    nb = 1 + A * NTAB
    k, v = (jnp.asarray(rng.normal(size=(nb, G, BL, d)), dtype)
            for _ in range(2))
    tables = (1 + rng.permutation(A * NTAB)).reshape(A, NTAB).astype(np.int32)
    tables[3] = 0
    lens = np.asarray([length, 7, NTAB * BL, 30, 2], np.int32)
    q = jnp.asarray(rng.normal(size=(A, G, J, d)), jnp.float32)
    got = np.asarray(paged.grouped_paged_attention(
        q, k, v, tables, lens, interpret=True)).reshape(A, -1)
    want = np.asarray(jax.jit(lm._full_decode, static_argnums=6)(
        q, k, v, jnp.asarray(tables), jnp.asarray(lens - 1),
        jnp.asarray(tables[:, 0] != 0), BL))
    assert M._DECODE_CHUNK == 8 and not got[3].any() and not want[3].any()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 if dtype == "float32" else 2e-2)


def _full_reads(pos, active, impl):
    """``full_keys_read`` of one decode step, by hand: the kernel reads each
    active row's blocks up to its position; the XLA path reads every active
    row to the last chunk (2 entries, 8 positions) any active row reaches."""
    if impl == "interpret":
        return sum(-(-(p + 1) // BL) * BL for p, on in zip(pos, active) if on)
    reach = max(p for p, on in zip(pos, active) if on)
    return sum(active) * min((reach // 8 + 1) * 8, 16 * BL)


def test_decode_through_the_kernel_equals_the_xla_path_across_a_wrap(
        folds_of_four):
    """``prefill_paged`` two rows (10 and 30 tokens: the window of 16 wraps
    twice by the end), then 18 ``decode_paged`` steps with
    ``impl="interpret"`` and with ``impl="xla"`` from the same state, an idle
    slot beside them, blocks in falling pool order: the logits agree to
    float32 rounding and the served tokens are the same; each path's
    ``full_keys_read`` is what it read, and ``full_keys_context`` the
    context, an active row and a full layer a step."""
    import jax
    lm, params = _lm()
    A, P = 3, 32
    seqs = np.stack([_ids(41, 64), _ids(42, 64), _ids(43, 64)])
    lens = np.asarray([10, 30, 0], np.int32)
    tables = np.ascontiguousarray(
        (1 + np.arange(A * 16, dtype=np.int32)).reshape(A, 16)[:, ::-1])
    tables[2] = 0
    state = jax.device_put(lm.init_paged_pools(1 + A * 16, BL, A))
    prompt = np.where(np.arange(P)[None] < lens[:, None], seqs[:, :P], 0)
    state, _ = _prefill(lm, params, state, prompt[:2], lens[:2],
                        tables[:2, :P // BL], range(2), BL)
    active = [True, True, False]
    out = {}
    for impl in ("interpret", "xla"):
        step = jax.jit(lambda *a, impl=impl: lm.decode_paged(
            *a, block_len=BL, impl=impl))
        st, pos, logits_all, read, ctx = state, lens.copy(), [], 0, 0
        for _ in range(18):
            toks = np.where(active, seqs[np.arange(A), pos], 0)
            logits, st = step(params, st, tables, pos, toks)
            logits_all.append(np.asarray(logits)[:2])
            read += _full_reads(pos, active, impl) * N_FULL
            ctx += sum(int(p) + 1 for p, on in zip(pos, active) if on) \
                * N_FULL
            pos = pos + np.asarray(active, np.int32)
        c = lm.paged_counters(st)
        assert c["full_keys_read"] == read, impl
        assert c["full_keys_context"] == ctx, impl
        out[impl] = np.stack(logits_all)
    np.testing.assert_allclose(out["interpret"], out["xla"], atol=LOGIT_TOL,
                               rtol=0)
    assert (out["interpret"].argmax(-1) == out["xla"].argmax(-1)).all()


def test_decode_paged_picks_the_full_layers_read_by_impl():
    """``pallas`` puts ONE Pallas call a full layer into the step (the
    grouped-page kernel) and runs every layer's routed experts through
    ``ops/expert_matmul``'s kernel, no ``ragged_dot`` left; ``xla`` has
    neither kernel: the window layers are XLA on both."""
    import jax
    lm, params = _lm()
    A = 2
    state = lm.init_paged_pools(1 + A * 16, BL, A)
    tables = (1 + np.arange(A * 16, dtype=np.int32)).reshape(A, 16)
    args = (params, state, tables, np.asarray([5, 9], np.int32),
            np.asarray([3, 4], np.int32))
    calls, experts = {}, {}
    for impl in ("pallas", "xla"):
        jaxpr = str(jax.make_jaxpr(lambda *a, impl=impl: lm.decode_paged(
            *a, block_len=BL, impl=impl))(*args))
        calls[impl] = jaxpr.count("name=grouped_paged_attention")
        experts[impl] = ("name=grouped_expert_ffn" in jaxpr,
                         "ragged_dot" in jaxpr)
    assert calls == {"pallas": N_FULL, "xla": 0}
    assert experts == {"pallas": (True, False), "xla": (False, True)}


# -- (c) the shared expert layer -----------------------------------------------

@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_routed_experts_over_complementary_shares_sum_to_the_whole_layer(
        scoring):
    """``lm_common.routed_experts`` over experts 0-2 and 3-7 (two chips'
    shares, every token routed over all 8) adds up to the layer that holds
    all 8; and that layer is the dense sum over the chosen experts."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.models import lm_common as common
    g = np.random.default_rng(7)
    T, H, F, E = 24, 16, 8, 8
    blk = {"router": g.normal(size=(H, E)).astype(np.float32),
           "e_bias": 0.1 * g.normal(size=(E,)).astype(np.float32),
           "w_gate": g.normal(size=(E, H, F)).astype(np.float32) / 4,
           "w_up": g.normal(size=(E, H, F)).astype(np.float32) / 4,
           "w_down": g.normal(size=(E, F, H)).astype(np.float32) / 4}
    h = g.normal(size=(T, H)).astype(np.float32)
    valid = np.arange(T) < 21
    act = jax.nn.silu if scoring == "sigmoid" else jax.nn.relu

    def share(first, count):
        part = dict(blk, **{n: blk[n][first:first + count]
                            for n in ("w_gate", "w_up", "w_down")})
        return common.routed_experts(
            jnp.asarray(h), jnp.asarray(h), part, jnp.asarray(valid),
            top_k=3, held=(first, count), dtype=jnp.float32, scoring=scoring,
            scale=2.5, act=act, slab=16)

    whole, cw = share(0, E)
    a, ca = share(0, 3)
    b, cb = share(3, 5)
    np.testing.assert_allclose(np.asarray(a) + np.asarray(b),
                               np.asarray(whole), atol=1e-5, rtol=1e-5)
    assert int(ca["held"]) + int(cb["held"]) == int(cw["held"]) \
        == int(cw["pairs"]) == 21 * 3
    # the dense form: every expert over every token, weighted where chosen
    logits = h @ blk["router"]
    s = 1 / (1 + np.exp(-logits)) if scoring == "sigmoid" else \
        np.exp(logits - logits.max(-1, keepdims=True))
    s = s / (1 if scoring == "sigmoid" else s.sum(-1, keepdims=True))
    pick = np.argsort(-(s + (blk["e_bias"] if scoring == "sigmoid" else 0)),
                      axis=-1, kind="stable")[:, :3]
    w = np.zeros((T, E))
    chosen = np.take_along_axis(s, pick, -1)
    np.put_along_axis(w, pick, 2.5 * chosen / chosen.sum(-1, keepdims=True),
                      -1)
    mid = np.asarray(act(np.einsum("th,ehf->etf", h, blk["w_gate"]))) \
        * np.einsum("th,ehf->etf", h, blk["w_up"])
    dense = np.einsum("te,eth->th", w * valid[:, None],
                      np.einsum("etf,efh->eth", mid, blk["w_down"]))
    np.testing.assert_allclose(np.asarray(whole), dense, atol=1e-4, rtol=1e-4)


# -- (d) through the unmodified scheduler --------------------------------------

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
    """Four requests (contexts 5-41 against the window 16) on two slots of
    an unmodified ``ContinuousBatcher``: every served token is the argmax of
    the reference's teacher-forced forward of prompt + served tokens."""
    _, _, params, reqs, tokens = served
    prompt = reqs[i][1]
    ids = np.concatenate([prompt, np.asarray(tokens[i], np.int32)])
    want = _ref_logits(params, ids, np.arange(len(prompt) - 1, len(ids) - 1))
    assert list(want.argmax(-1)) == tokens[i]


def test_the_counters_total_what_the_requests_needed(served):
    b, _, _, reqs, tokens = served
    c = b.stats()
    got = {k[len("model."):]: v for k, v in c.items()
           if k.startswith("model.")}
    ctx = [len(p) + j + 1 for (_, p, _), toks in zip(reqs, tokens)
           for j in range(len(toks) - 1)]
    assert got["window_keys_context"] == sum(ctx) * N_WINDOW
    assert got["window_keys_attended"] == sum(min(n, WINDOW) for n in ctx) \
        * N_WINDOW
    assert got["moe_pairs"] == (sum(len(p) for _, p, _ in reqs) + len(ctx)) \
        * TOP_K * 4
    assert got["moe_layer_steps"] == c["decode_steps"] * 4
    assert 0 < got["moe_experts_touched"] <= got["moe_layer_steps"] * 8
    lane = b._lanes[0]
    assert set(lane.state) == {"k", "v", "rk", "rv", "counters"}
    doc = b.state_bytes_doc()
    n_blocks = b._pool.n_blocks + 1
    assert doc["paged_pool"] == N_FULL * 2 * n_blocks * 2 * 4 * 8 * 4
    assert doc["lanes"] == N_WINDOW * 2 * 2 * 2 * WINDOW * 8 * 4 \
        + 10 * 2 * 4


def test_prefix_cache_is_refused_at_start(served):
    _, lm, params, _, _ = served
    with pytest.raises(ValueError, match="prefix_cache"):
        _batcher(lm, params, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="shared prefix"):
        lm.prefill_shared_paged(params, {}, None, None, None, None, None,
                                None, block_len=4)
