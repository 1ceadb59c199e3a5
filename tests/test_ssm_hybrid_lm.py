"""``models/ssm_hybrid_lm.SSMHybridLM`` and ``ops/selective_scan`` against the
plain reference (``benchmark/reference_ssm_hybrid.py``, which calls no model
code), at tiny widths in FLOAT32 on the CPU, with the REAL structure: 12
layers placed by ``mb_per_layer`` 2 (Mamba and window layers in turn up to a
Mamba layer at N/2, the full layer, then two (GMU, cross) pairs), 8 heads
over 4 key heads (2 key pairs, 4 query rows a pair), and a window (8) short
enough that contexts pass it four times.  The program's chunk sizes are cut
to the toy's, so that every loop runs more than once: positions 16 a chunk
(the Mamba state crosses chunks), queries 8 a block, window keys 4 a chunk,
the scan 4 positions a trip.

TOLERANCE.  Both sides compute in float32 (the reference at ``highest``), so
they differ by summation order only: ``call`` against the reference measured
1.0e-4 - 5.3e-4 on logits of magnitude ~10, over four weight seeds and two
sequences (the toy's weights, std 0.3, make large activations that 12 layers
of norms carry).  ``LOGIT_TOL`` = 2e-3 leaves ~4 x of room and is 300 x under
what bfloat16 operands read (``test_a_lower_precision_fails_the_tolerance``).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

LOGIT_TOL = 2e-3
WINDOW = 8
CFG = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    num_hidden_layers=12, num_attention_heads=8, num_key_value_heads=4,
    sliding_window=WINDOW, mb_per_layer=2, mamba_d_state=4, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank=4, layer_norm_eps=1e-5,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    max_position_embeddings=64)
N_MAMBA, N_WINDOW, N_CROSS = 4, 3, 2
READERS = 1 + N_CROSS                      # the full layer and the cross layers
BL = 4

_BUILT = {}


def _lm():
    """``(model, weights)``, built once; the chunk sizes cut to the toy's."""
    import jax
    from analytics_zoo_tpu.models import ssm_hybrid_lm as M
    if not _BUILT:
        M._POS_CHUNK, M._QUERY_BLOCK, M._KEY_CHUNK = 16, 8, 16
        M._WINDOW_CHUNK, M._SCAN_UNROLL = 4, 4
        lm = M.SSMHybridLM.from_config(CFG, dtype="float32",
                                       initializer_range=0.3)
        _BUILT["lm"] = lm, jax.jit(lm.build)(jax.random.PRNGKey(0))
    return _BUILT["lm"]


def _ref_logits(params, ids, rows=None, **kw):
    """The reference over ``ids`` right-padded to ONE length (every layer is
    causal, so the padding is harmless): its layers compile once."""
    import reference_ssm_hybrid as ref
    padded = np.zeros((64,), np.int32)
    padded[:len(ids)] = ids
    return ref.logits(params, CFG, padded,
                      np.arange(len(ids)) if rows is None else rows, **kw)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


def _prefill(lm, params, state, prompt, lens, dest, slots):
    import jax
    return jax.jit(lambda *a: lm.prefill_paged(*a, block_len=BL))(
        params, state, prompt, np.asarray(lens, np.int32), dest,
        np.asarray(slots, np.int32))


def _decode(lm, impl="xla"):
    import jax
    return jax.jit(lambda *a: lm.decode_paged(*a, block_len=BL, impl=impl))


def _pools(lm, A, ntab=16):
    import jax
    return jax.device_put(lm.init_paged_pools(1 + A * ntab, BL, A))


# -- (a) the layer kinds and the full forward ----------------------------------

def test_layer_kinds_come_from_the_configuration():
    """At the published keys (32 layers, ``mb_per_layer`` 2): 9 Mamba layers
    (0, 2 .. 16), 8 window layers (1 .. 15), the full layer 17, 7 GMU layers
    (18 .. 30) and 7 cross layers (19 .. 31); the toy's 12 layers the same
    pattern at its depth."""
    from analytics_zoo_tpu.models.ssm_hybrid_lm import layer_kinds
    kinds = layer_kinds(32, 2)
    assert "".join(kinds) == "MW" * 8 + "MF" + "GC" * 7
    assert [l for l, k in enumerate(kinds) if k == "M"] == list(range(0, 17, 2))
    lm, _ = _lm()
    assert "".join(lm.kinds) == "MWMWMWMFGCGC"
    assert (lm.n_pairs, lm.n_window, lm.n_cross) == (N_MAMBA, N_WINDOW,
                                                     N_CROSS)
    assert (lm.head_dim, lm.n_groups, lm.rows) == (8, 2, 4)
    assert (lm.d_inner, lm.dt_rank, lm.window) == (128, 4, WINDOW)


@pytest.mark.parametrize("change", [dict(mb_per_layer=3),
                                    dict(num_hidden_layers=10),
                                    dict(num_key_value_heads=3),
                                    dict(tie_word_embeddings=False)],
                         ids=["mb_3", "depth_10", "odd_key_heads", "untied"])
def test_configurations_the_class_cannot_serve_are_refused(change):
    from analytics_zoo_tpu.models.ssm_hybrid_lm import SSMHybridLM
    with pytest.raises(ValueError):
        SSMHybridLM.from_config(dict(CFG, **change))


@pytest.mark.parametrize("seed", [1, 2])
def test_call_equals_the_reference(seed):
    """``call`` over 64 positions (8 windows, 4 chunks) against the
    reference's forward: the Mamba recurrence position by position, window
    masks over the full score rows, the cross-decoder over every position."""
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


# -- (b) the selective scan's two forms ----------------------------------------

def _scan_inputs(seed, T=20, D=24, N=4, K=4):
    import jax.numpy as jnp
    g = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(g.normal(size=s), jnp.float32)  # noqa: E731
    return dict(u=f(T, D), delta=jnp.abs(f(T, D)) * 0.3,
                A=-jnp.exp(f(N, D) * 0.5), B=f(T, N), C=f(T, N),
                w=f(K, D), b=f(D), T=T, D=D, N=N, K=K)


@pytest.mark.parametrize("chunks", [(20,), (8, 8, 4), (4, 4, 4, 4, 4)],
                         ids=["one", "three", "five"])
def test_the_chunked_scan_and_convolution_equal_their_step_forms(chunks):
    """``scan_chunk`` / ``conv_chunk`` over a sequence cut into chunks, the
    state and the convolution's carry handed from chunk to chunk, against
    ``scan_step`` / ``conv_step`` one position at a time."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import selective_scan as ss
    x = _scan_inputs(3)
    s, carry = jnp.zeros((x["N"], x["D"])), jnp.zeros((x["K"] - 1, x["D"]))
    ys, outs, lo = [], [], 0
    for n in chunks:
        sl = slice(lo, lo + n)
        out, carry = ss.conv_chunk(x["u"][sl], x["w"], x["b"], carry, n)
        y, s = ss.scan_chunk(x["u"][sl], x["delta"][sl], x["A"], x["B"][sl],
                             x["C"][sl], s, unroll=3)
        ys.append(y)
        outs.append(out)
        lo += n
    s1, c1, ys1, outs1 = jnp.zeros((1, x["N"], x["D"])), \
        jnp.zeros((1, x["K"] - 1, x["D"])), [], []
    for t in range(x["T"]):
        out, c1 = ss.conv_step(x["u"][t:t + 1], x["w"], x["b"], c1)
        y, s1 = ss.scan_step(x["u"][t:t + 1], x["delta"][t:t + 1], x["A"],
                             x["B"][t:t + 1], x["C"][t:t + 1], s1)
        ys1.append(y)
        outs1.append(out)
    np.testing.assert_allclose(np.concatenate(ys), np.concatenate(ys1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.concatenate(outs), np.concatenate(outs1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s, s1[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(carry, c1[0])


def test_a_step_of_zero_leaves_the_state_exactly_as_it_was():
    """Padding (``delta`` 0 past a row's length) neither enters the state nor
    decays it: the state after 13 real and 7 padded positions is the state
    after the 13 alone, bit for bit; the convolution's carry is the 3 inputs
    that end with the 13th."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import selective_scan as ss
    x = _scan_inputs(4)
    s0 = jnp.zeros((x["N"], x["D"]))
    live = jnp.arange(x["T"])[:, None] < 13
    _, padded = ss.scan_chunk(x["u"], jnp.where(live, x["delta"], 0.0),
                              x["A"], x["B"], x["C"], s0, unroll=4)
    _, alone = ss.scan_chunk(x["u"][:13], x["delta"][:13], x["A"],
                             x["B"][:13], x["C"][:13], s0, unroll=4)
    np.testing.assert_array_equal(padded, alone)
    _, carry = ss.conv_chunk(x["u"], x["w"], x["b"],
                             jnp.zeros((x["K"] - 1, x["D"])), 13)
    np.testing.assert_array_equal(carry, x["u"][10:13])


# -- (c) prefill, then decode through the paged state --------------------------

@pytest.mark.parametrize("lengths", [(10, 16), (30, 7), (32, 21)],
                         ids=["one_chunk", "two_chunks", "whole_bucket"])
def test_prefill_then_decode_equal_the_references_forward(lengths):
    """``prefill_paged`` (a bucket of 32: two chunks of 16, the Mamba state
    handed across) then 16 ``decode_paged`` steps a row, logits against the
    reference's forward over the whole sequence: contexts past the window
    (8) several times over, so the rings fill and wrap, across block
    boundaries (every 4th position), a row's padding beside the other's
    real positions.  The counters total what the steps read."""
    lm, params = _lm()
    A, P = 2, 32
    seqs = np.stack([_ids(11, 64), _ids(12, 64)])
    lens = np.asarray(lengths, np.int32)
    want = [_ref_logits(params, seqs[a]) for a in range(A)]
    state = _pools(lm, A)
    tables = 1 + np.arange(A * 16, dtype=np.int32).reshape(A, 16)
    prompt = np.where(np.arange(P)[None] < lens[:, None], seqs[:, :P], 0)
    state, logits0 = _prefill(lm, params, state, prompt, lens,
                              tables[:, :P // BL], range(A))
    for a in range(A):
        np.testing.assert_allclose(np.asarray(logits0)[a],
                                   want[a][lens[a] - 1], atol=LOGIT_TOL,
                                   rtol=0)
    step = _decode(lm)
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
    assert c["full_keys_context"] == sum(ctx) * READERS
    # the XLA path gathers every row's whole table (16 blocks of 4)
    assert c["full_keys_read"] == len(ctx) * 16 * BL * READERS


@pytest.mark.parametrize("length", [5, 16, 27])
def test_the_yoco_prefill_equals_a_full_depth_prefill(length):
    """The prefill runs the cross-decoder over the row's last position only;
    its logits are those of ``call``, which runs it over every position, and
    the reference's, at that position."""
    import jax
    lm, params = _lm()
    ids = _ids(21, 32)
    prompt = np.where(np.arange(32) < length, ids, 0)
    _, got = _prefill(lm, params, _pools(lm, 1), prompt[None], [length],
                      1 + np.arange(8, dtype=np.int32)[None], [0])
    full = np.asarray(jax.jit(lm.call)(params, prompt[None]))[0]
    np.testing.assert_allclose(np.asarray(got)[0], full[length - 1],
                               atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(got)[0],
                               _ref_logits(params, ids[:length])[-1],
                               atol=LOGIT_TOL, rtol=0)


def test_the_prefill_counters_read_what_the_cells_metrics_expect():
    """Two rows (5 and 27 of a 32 bucket) and a padding row: real positions
    32; the cross-decoder's layer-positions 2 rows x 4 layers (so
    ``yoco.prefill_cross_share`` reads 100 x 2 / 32); the Mamba layers
    scanned one chunk of 16 for the first row and two for the second."""
    lm, params = _lm()
    state = _pools(lm, 3)
    ids = _ids(22, 32)
    prompt = np.stack([np.where(np.arange(32) < n, ids, 0) for n in (5, 27, 5)])
    dest = np.zeros((3, 8), np.int32)
    dest[0], dest[1] = 1 + np.arange(8), 17 + np.arange(8)
    state, _ = _prefill(lm, params, state, prompt, [5, 27, 5], dest,
                        [0, 1, 3])
    c = lm.paged_counters(state)
    assert c["prefill_positions_real"] == 32
    assert c["prefill_cross_positions"] == 2 * 2 * N_CROSS
    assert c["ssm_positions_real"] == 32 * N_MAMBA
    assert c["ssm_positions_scanned"] == (16 + 32) * N_MAMBA
    share = 100 * c["prefill_cross_positions"] / (
        2 * N_CROSS * c["prefill_positions_real"])
    assert share == 100 * 2 / 32


def test_a_padding_row_and_an_idle_slot_leave_no_trace():
    """A batch's padding row (blocks all trash, slot = the drop sentinel) is
    skipped whole: the real row's state is what it would be alone, bit for
    bit, and the other slot's rings and Mamba state stay zero.  An idle
    slot's decode step (table all trash) leaves its rings and Mamba state as
    they were, and counts nothing."""
    lm, params = _lm()
    A = 2
    ids = _ids(3, 32)
    dest = np.zeros((2, 8), np.int32)
    dest[0] = 1 + np.arange(8)
    both, _ = _prefill(lm, params, _pools(lm, A), np.stack([ids, ids]),
                       [20, 20], dest, [0, A])
    alone, _ = _prefill(lm, params, _pools(lm, A), ids[None], [20],
                        dest[:1], [0])
    for name in ("k", "v", "rk", "rv", "conv", "ssm"):
        for x, y in zip(both[name], alone[name]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for name in ("rk", "conv", "ssm"):
        assert np.abs(np.asarray(both[name][0])[0]).max() > 0
        assert not np.asarray(both[name][0])[1].any()
    tables = np.zeros((A, 16), np.int32)
    tables[0] = 1 + np.arange(16)
    _, after = _decode(lm)(params, both, tables, np.asarray([20, 5], np.int32),
                           np.asarray([7, 9], np.int32))
    for name in ("rk", "rv", "conv", "ssm"):
        for now in after[name]:
            assert not np.asarray(now)[1].any(), name
    c0, c1 = lm.paged_counters(both), lm.paged_counters(after)
    assert c1["window_keys_context"] - c0["window_keys_context"] \
        == 21 * N_WINDOW


def test_a_slot_reused_after_a_longer_request_gives_a_fresh_slots_logits():
    """Slot 0 serves a 40-token context first; then a 6-token prompt in the
    same slot (its rings and Mamba state hold the longer request's) decodes
    12 steps: the logits are a fresh state's, bit for bit."""
    lm, params = _lm()
    tables = 1 + np.arange(16, dtype=np.int32)[None]
    step = _decode(lm)

    def serve(state, ids, n, steps):
        prompt = np.zeros((1, 32), np.int32)
        prompt[0, :n] = ids[:n]
        state, first = _prefill(lm, params, state, prompt, [n], tables[:, :8],
                                [0])
        out, pos = [np.asarray(first)[0]], np.asarray([n], np.int32)
        for _ in range(steps):
            logits, state = step(params, state, tables, pos, ids[pos])
            out.append(np.asarray(logits)[0])
            pos = pos + 1
        return state, np.stack(out)

    used, _ = serve(_pools(lm, 1), _ids(31, 64), 30, 10)
    short = _ids(32, 64)
    _, again = serve(used, short, 6, 12)
    _, fresh = serve(_pools(lm, 1), short, 6, 12)
    np.testing.assert_array_equal(again, fresh)


def test_the_mamba_state_keeps_float32_under_bfloat16_weights():
    """Served in bfloat16, the ``conv`` and ``ssm`` leaves are float32 as
    made, after a prefill and after a decode step (a state held in the
    weights' type would be rounded at every token; ``correct`` cannot see
    that at the cell's lengths, PERF.md section 7); the pages and rings are
    in the weights' type."""
    import jax
    from analytics_zoo_tpu.models.ssm_hybrid_lm import SSMHybridLM
    lm = SSMHybridLM.from_config(CFG)                      # bfloat16
    params = jax.jit(lm.build)(jax.random.PRNGKey(1))
    state = _pools(lm, 1)
    ids = _ids(5, 32)
    tables = 1 + np.arange(16, dtype=np.int32)[None]
    state, _ = _prefill(lm, params, state, ids[None], [20], tables[:, :8],
                        [0])
    _, state2 = _decode(lm)(params, state, tables, np.asarray([20], np.int32),
                            np.asarray([3], np.int32))
    for st in (state, state2):
        assert {str(a.dtype) for a in st["conv"] + st["ssm"]} == {"float32"}
        assert {str(a.dtype) for a in st["k"] + st["rk"]} == {"bfloat16"}
    assert np.abs(np.asarray(state2["ssm"][0])).max() > 0


# -- (d) the pages' read: the grouped-page kernel against the XLA path ---------

def test_decode_through_the_kernel_equals_the_xla_path_across_a_wrap():
    """``prefill_paged`` two rows (10 and 30 tokens), then 14 ``decode_paged``
    steps with ``impl="interpret"`` (the grouped-page kernel reading the key
    pairs as 2d-wide key heads, the query pairs as rows ``[q1 | 0]``,
    ``[0 | q2]``) and with ``impl="xla"`` from the same state, an idle slot
    beside them, blocks in falling pool order: the logits agree to float32
    rounding; each path's ``full_keys_read`` is what it read (the kernel a
    row's live blocks, the XLA path its whole table), a reader a row."""
    import jax
    lm, params = _lm()
    A, P = 3, 32
    seqs = np.stack([_ids(41, 64), _ids(42, 64), _ids(43, 64)])
    lens = np.asarray([10, 30, 0], np.int32)
    tables = np.ascontiguousarray(
        (1 + np.arange(A * 16, dtype=np.int32)).reshape(A, 16)[:, ::-1])
    tables[2] = 0
    prompt = np.where(np.arange(P)[None] < lens[:, None], seqs[:, :P], 0)
    state, _ = _prefill(lm, params, _pools(lm, A), prompt[:2], lens[:2],
                        tables[:2, :P // BL], range(2))
    active = [True, True, False]
    out = {}
    for impl in ("interpret", "xla"):
        step = _decode(lm, impl)
        st, pos, got, read = state, lens.copy(), [], 0
        for _ in range(14):
            toks = np.where(active, seqs[np.arange(A), pos], 0)
            logits, st = step(params, st, tables, pos, toks)
            got.append(np.asarray(logits)[:2])
            read += sum((-(-(p + 1) // BL) * BL if impl == "interpret"
                         else 16 * BL) for p, on in zip(pos, active) if on)
            pos = pos + np.asarray(active, np.int32)
        assert lm.paged_counters(st)["full_keys_read"] == read * READERS
        out[impl] = np.stack(got)
    np.testing.assert_allclose(out["interpret"], out["xla"], atol=LOGIT_TOL,
                               rtol=0)
    assert (out["interpret"].argmax(-1) == out["xla"].argmax(-1)).all()


def test_decode_paged_reads_the_pages_through_the_kernel_by_impl():
    """``pallas`` puts ONE Pallas call into the step for each reader of the
    pages (the full layer and every cross layer); ``xla`` none."""
    import jax
    lm, params = _lm()
    A = 2
    state = lm.init_paged_pools(1 + A * 16, BL, A)
    tables = (1 + np.arange(A * 16, dtype=np.int32)).reshape(A, 16)
    args = (params, state, tables, np.asarray([5, 9], np.int32),
            np.asarray([3, 4], np.int32))
    def kernels(jaxpr):
        # the kernel's calls share ONE traced body: count the calls
        return sum(e.params.get("name") == "_grouped_pallas"
                   or "name=grouped_paged_attention" in str(e)
                   for e in jaxpr.eqns)

    calls = {impl: kernels(jax.make_jaxpr(
        lambda *a, impl=impl: lm.decode_paged(*a, block_len=BL, impl=impl))(
            *args).jaxpr) for impl in ("pallas", "xla")}
    assert calls == {"pallas": READERS, "xla": 0}


@pytest.mark.parametrize("grown", ["depth", "length"])
def test_a_prefill_program_holds_one_pairs_code(grown):
    """Twice the depth (16 layers for 12: two more pairs each side), or twice
    the bucket, adds no matmul to the lowered prefill program: the
    self-decoder's pairs and the cross-decoder's run in scans, chunks and
    key chunks in loops (unrolled layers made a serving start pay for every
    layer's lowering and compile)."""
    import jax
    from analytics_zoo_tpu.models.ssm_hybrid_lm import SSMHybridLM
    _lm()                                  # the toy's chunk sizes

    def matmuls(n_layers, S):
        lm = SSMHybridLM.from_config(dict(CFG, num_hidden_layers=n_layers),
                                     dtype="float32")
        params = jax.eval_shape(lm.build, jax.random.PRNGKey(0))
        state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             lm.init_paged_pools(1 + S // BL, BL, 1))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32)  # noqa: E731
        text = jax.jit(lambda *a: lm.prefill_paged(*a, block_len=BL)).lower(
            params, state, i32(1, S), i32(1), i32(1, S // BL), i32(1)
        ).as_text()
        return text.count("stablehlo.dot_general")

    assert matmuls(12, 32) > 0
    assert matmuls(12, 32) == (matmuls(16, 32) if grown == "depth"
                               else matmuls(12, 64))
