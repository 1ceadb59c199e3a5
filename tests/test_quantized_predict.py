"""Fused-dequant quantized predict (PR 14): Pallas kernel parity vs the
XLA oracle, int4 packing + group-wise calibration, the path-keyed
calibration fix, quantized weight-store round-trips, sharding-plan
consistency, and warm quantized serving with zero steady-state compiles."""

import json
import os
import sys

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.inference import aot, weightstore
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.inference import quantize as qz
from analytics_zoo_tpu.ops import quant_matmul as qm

pytestmark = pytest.mark.quant


def _mlp_conv_model():
    """Fixed-seed conv + dense classifier (the accuracy-golden model).
    Seeded via an EXPLICIT rng — mutating the global context here would
    leak into later tests that draw init streams from it."""
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Convolution2D, Dense, Flatten
    m = Sequential()
    m.add(Convolution2D(8, 3, activation="relu", border_mode="same",
                        input_shape=(8, 8, 3)))
    m.add(Flatten())
    m.add(Dense(32, activation="relu"))
    m.add(Dense(5, activation="softmax"))
    m.init_weights(rng=jax.random.PRNGKey(7))
    return m


def _mlp_model(inp=16, out=8):
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(out, activation="softmax", input_shape=(inp,)))
    m.init_weights()
    return m


# -- int4 packing --------------------------------------------------------------

def test_pack_unpack_int4_roundtrip(rng):
    for k, n in ((16, 9), (13, 4), (1, 3), (256, 12)):
        q = rng.integers(-7, 8, (k, n)).astype(np.int8)
        packed = qm.pack_int4(q)
        assert packed.dtype == np.uint8
        assert packed.shape == ((k + 1) // 2, n)
        assert np.array_equal(np.asarray(qm.unpack_int4(packed, k)), q)


# -- kernel parity vs the XLA oracle -------------------------------------------

def test_w8a8_kernel_bitwise_vs_oracle(rng):
    """s8 x s8 -> s32 is exact, and the kernel dequantizes with the same
    f32 expression as the oracle — outputs must match BITWISE, including
    padded/unaligned shapes."""
    for m, k, n in ((5, 200, 17), (1, 16, 8), (130, 384, 129), (32, 7, 3)):
        x_q = rng.integers(-127, 128, (m, k)).astype(np.int8)
        w_q = rng.integers(-127, 128, (k, n)).astype(np.int8)
        scale = (rng.random(n).astype(np.float32) + 0.1) * 0.01
        ref = np.asarray(qm.w8a8_matmul(x_q, w_q, scale, impl="xla"))
        ker = np.asarray(qm.w8a8_matmul(x_q, w_q, scale, impl="interpret"))
        assert np.array_equal(ref, ker), (m, k, n)


def test_w4a16_kernel_vs_oracle_tolerance(rng):
    """f32 accumulation order differs between the group loop and the
    oracle's single matmul: equality within float tolerance."""
    for m, k, n, g in ((3, 256, 12, 2), (9, 512, 64, 4), (16, 1024, 8, 8)):
        q = rng.integers(-7, 8, (k, n)).astype(np.int8)
        packed = qm.pack_int4(q)
        s_g = (rng.random((g, n)).astype(np.float32) + 0.05) * 0.1
        x = rng.standard_normal((m, k)).astype(np.float32)
        ref = np.asarray(qm.w4a16_matmul(x, packed, s_g, impl="xla"))
        ker = np.asarray(qm.w4a16_matmul(x, packed, s_g, impl="interpret"))
        np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-4)


def test_w4a16_unaligned_is_the_oracle_only_under_auto(rng):
    # ragged groups / odd K are outside the kernel's alignment contract:
    # impl="auto" computes such a shape through the XLA reference (the
    # shape is the caller's to see); an explicit kernel request raises
    # instead of being demoted behind the caller's back
    k, n = 100, 8
    q = rng.integers(-7, 8, (k, n)).astype(np.int8)
    packed = qm.pack_int4(q)
    s_g = np.full((3, n), 0.1, np.float32)          # gs=34: ragged
    x = rng.standard_normal((4, k)).astype(np.float32)
    out = np.asarray(qm.w4a16_matmul(x, packed, s_g))
    ref = np.asarray(qm.w4a16_matmul_xla(x, packed, s_g))
    assert np.array_equal(out, ref)
    with pytest.raises(ValueError, match="w4a16 kernel needs"):
        qm.w4a16_matmul(x, packed, s_g, impl="interpret")


def test_w8a8_pointwise_conv_routes_through_matmul_kernel(rng):
    """A 1x1/stride-1 conv IS a channel matmul: the kernel route and the
    general int8 conv agree bitwise (both are exact integer accumulation
    with the identical output dequant)."""
    b, h, w, cin, cout = 2, 4, 4, 24, 10
    x_q = rng.integers(-127, 128, (b, h, w, cin)).astype(np.int8)
    w_q = rng.integers(-127, 128, (1, 1, cin, cout)).astype(np.int8)
    scale = (rng.random(cout).astype(np.float32) + 0.1) * 0.01
    dn = jax.lax.conv_dimension_numbers(
        (1, 1, 1, 1), (1, 1, 1, 1), ("NHWC", "HWIO", "NHWC"))
    kw = dict(window_strides=(1, 1), padding="VALID",
              rhs_dilation=(1, 1), dimension_numbers=dn)
    routed = np.asarray(qm.w8a8_conv(x_q, w_q, scale, impl="interpret",
                                     **kw))
    acc = jax.lax.conv_general_dilated(
        x_q, w_q, preferred_element_type=np.int32, **kw)
    general = np.asarray(acc).astype(np.float32) * scale
    assert np.array_equal(routed, general)


def test_pointwise_conv_with_explicit_padding_stays_on_conv_path(rng):
    """Review regression: a 1x1 conv with caffe-style EXPLICIT padding
    grows the output spatially — it must not route through the
    flatten-to-matmul fast path (which cannot pad)."""
    b, h, w, cin, cout = 2, 4, 4, 8, 8
    x_q = rng.integers(-127, 128, (b, h, w, cin)).astype(np.int8)
    w_q = rng.integers(-127, 128, (1, 1, cin, cout)).astype(np.int8)
    scale = np.full(cout, 0.01, np.float32)
    dn = jax.lax.conv_dimension_numbers(
        (1, 1, 1, 1), (1, 1, 1, 1), ("NHWC", "HWIO", "NHWC"))
    kw = dict(window_strides=(1, 1), padding=[(1, 1), (1, 1)],
              rhs_dilation=(1, 1), dimension_numbers=dn)
    out = np.asarray(qm.w8a8_conv(x_q, w_q, scale, impl="interpret", **kw))
    acc = jax.lax.conv_general_dilated(
        x_q, w_q, preferred_element_type=np.int32, **kw)
    want = np.asarray(acc).astype(np.float32) * scale
    assert out.shape == (b, h + 2, w + 2, cout)
    assert np.array_equal(out, want)
    # zero explicit padding IS pointwise and still matches
    kw0 = dict(kw, padding=[(0, 0), (0, 0)])
    out0 = np.asarray(qm.w8a8_conv(x_q, w_q, scale, impl="interpret",
                                   **kw0))
    acc0 = jax.lax.conv_general_dilated(
        x_q, w_q, preferred_element_type=np.int32, **kw0)
    assert np.array_equal(out0, np.asarray(acc0).astype(np.float32) * scale)


def test_w4a16_ragged_group_division_falls_back(rng):
    """Review regression: group counts that do not divide K exactly
    (floor-vs-ceil group size ambiguity) are OUTSIDE the kernel contract
    and must never mis-slice silently: auto computes them through the XLA
    reference, an explicit kernel request raises."""
    k, n, g = 2048, 8, 66                    # ceil gs 32 but floor gs 31
    assert not qm._w4_pallas_ok(k, g)
    q = rng.integers(-7, 8, (k, n)).astype(np.int8)
    packed = qm.pack_int4(q)
    s_g = (rng.random((g, n)).astype(np.float32) + 0.05) * 0.1
    x = rng.standard_normal((3, k)).astype(np.float32)
    out = np.asarray(qm.w4a16_matmul(x, packed, s_g))
    ref = np.asarray(qm.w4a16_matmul_xla(x, packed, s_g))
    assert np.array_equal(out, ref)
    with pytest.raises(ValueError, match="w4a16 kernel needs"):
        qm.w4a16_matmul(x, packed, s_g, impl="interpret")


# -- calibration: path keying (collision fix), percentile, FeatureSet ----------

def test_calibration_keyed_by_path_duplicate_names(rng):
    """Satellite regression: two same-named layers in different containers
    used to share one absmax (records keyed by bare name) and the first
    located sub-dict won (locate() by depth-first name search) — both now
    calibrate and quantize independently, keyed by path."""
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    inner_a = Sequential(name="blk_a")
    inner_a.add(Dense(6, input_shape=(4,), name="dup"))
    inner_b = Sequential(name="blk_b")
    inner_b.add(Dense(6, input_shape=(6,), name="dup"))
    m = Sequential()
    m.add(inner_a)
    m.add(inner_b)
    m.init_weights()
    x = rng.standard_normal((16, 4)).astype(np.float32) * 3.0
    y_fp = np.asarray(m.predict(x))
    absmax = qz.calibrate(m, m._params, m._state, np.asarray(x))
    assert set(absmax) == {"blk_a/dup", "blk_b/dup"}
    assert absmax["blk_a/dup"] != absmax["blk_b/dup"]
    qp = qz.quantize_params(m, m._params, absmax)
    # BOTH layers quantized (the old first-holder-wins bug left one float,
    # and wrote the winner twice)
    for blk, path in (("blk_a", "blk_a/dup"), ("blk_b", "blk_b/dup")):
        lp = qp[blk]["dup"]
        assert "W_q" in lp and "W" not in lp
        assert float(lp["s_x"]) * 127.0 == pytest.approx(absmax[path])
    y_q = np.asarray(m.apply(qp, m._state, np.asarray(x),
                             training=False)[0])
    assert np.abs(y_q - y_fp).max() < 0.2


def test_percentile_clip_tightens_activation_scale(rng):
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(4, input_shape=(8,), name="d0"))
    m.init_weights()
    x = rng.standard_normal((256, 8)).astype(np.float32)
    x[0, 0] = 500.0                       # one wild outlier
    plain = qz.calibrate(m, m._params, m._state, np.asarray(x))
    clipped = qz.calibrate(m, m._params, m._state, np.asarray(x),
                           percentile=99.0)
    assert plain["d0"] == pytest.approx(500.0)
    assert clipped["d0"] < 50.0           # the outlier no longer sets s_x
    with pytest.raises(ValueError):
        qz.calibrate(m, m._params, m._state, np.asarray(x), percentile=0.0)
    # long sweeps fold the retained |x| sample down (bounded memory) and
    # still produce a sane clip
    many = [np.asarray(rng.standard_normal((64, 8)).astype(np.float32))
            for _ in range(12)]
    swept = qz.calibrate(m, m._params, m._state, many, percentile=99.0)
    assert 0.0 < swept["d0"] <= plain["d0"]


def test_calibrate_featureset_draws_n_batches(rng):
    from analytics_zoo_tpu.feature.dataset import FeatureSet
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(4, input_shape=(8,), name="d0"))
    m.init_weights()
    x = rng.standard_normal((128, 8)).astype(np.float32)
    x[-1] = 1000.0                        # outlier in the LAST batch only
    fs = FeatureSet.from_arrays(x, np.zeros((128, 1), np.float32))
    absmax = qz.calibrate_featureset(m, m._params, m._state, fs,
                                     n_batches=2, batch_size=32)
    assert absmax["d0"] < 100.0           # batches 3+ never drawn
    full = qz.calibrate_featureset(m, m._params, m._state, fs,
                                   n_batches=8, batch_size=32)
    assert full["d0"] == pytest.approx(1000.0)
    # int8 quantization straight from the FeatureSet sample
    qp = qz.quantize(m, m._params, m._state, fs)
    assert "W_q" in qp["d0"]


# -- accuracy goldens ----------------------------------------------------------

def test_int8_accuracy_golden(rng):
    m = _mlp_conv_model()
    x = np.random.default_rng(11).standard_normal(
        (64, 8, 8, 3)).astype(np.float32)
    im_fp = InferenceModel().do_load_model(m, m._params, m._state)
    y_fp = im_fp.do_predict(x)
    im_q = InferenceModel().do_load_model(m, m._params, m._state)
    im_q.do_quantize(x[:32], force=True, bits=8)
    y_q = im_q.do_predict(x)
    # the golden model is untrained (razor-thin class margins — the
    # hardest top-1 regime); trained models hold >= 0.99, see
    # test_int8_quantize.test_quantize_via_inference_model_top1_parity
    assert (y_q.argmax(-1) == y_fp.argmax(-1)).mean() >= 0.95
    assert np.abs(y_q - y_fp).max() < 0.06
    assert qz.quantized_bits(im_q._params) == 8


def test_int4_groupwise_within_documented_tolerance(rng):
    """int4 group-wise carries looser (documented) tolerances than int8:
    top-1 agreement >= 0.9, probabilities within 0.15.  (The golden model
    is untrained, so its class margins are razor-thin — the hardest
    regime for weight-only int4; trained models with real margins hold
    agreement near 1.0, see the bench accuracy-delta field.)"""
    m = _mlp_conv_model()
    x = np.random.default_rng(11).standard_normal(
        (64, 8, 8, 3)).astype(np.float32)
    im_fp = InferenceModel().do_load_model(m, m._params, m._state)
    y_fp = im_fp.do_predict(x)
    im_q = InferenceModel().do_load_model(m, m._params, m._state)
    im_q.do_quantize(None, force=True, bits=4, group_size=64)
    y_q = im_q.do_predict(x)
    assert (y_q.argmax(-1) == y_fp.argmax(-1)).mean() >= 0.9
    assert np.abs(y_q - y_fp).max() < 0.15
    assert qz.quantized_bits(im_q._params) == 4
    # two weights per byte, packed uint8 + f32 group scales
    leaves = {p.rsplit("/", 1)[-1]: l for p, l in qz._leaf_items(
        im_q._params)}
    assert np.dtype(leaves["W_q4"].dtype) == np.uint8
    assert np.dtype(leaves["s_g"].dtype) == np.float32


def test_group_size_normalization(rng):
    """The requested group size normalizes to ceil(K/ceil(K/gs)) so the
    effective size is derivable from stored shapes alone — jitted
    consumers reconstruct it without a side-channel leaf."""
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(6, input_shape=(100,), name="d0"))   # K=100
    m.init_weights()
    qp = qz.quantize_params(m, m._params, {}, bits=4, group_size=64)
    s_g = qp["d0"]["s_g"]
    assert s_g.shape[0] == 2                          # ceil(100/64)
    # ceil(K/G) = 50: expansion reproduces the quantizer's boundaries
    rows = np.asarray(qm.expand_group_scales(s_g, 100))
    assert rows.shape == (100, 6)
    assert np.array_equal(rows[:50], np.broadcast_to(
        np.asarray(s_g)[0], (50, 6)))


# -- HBM-traffic accounting ----------------------------------------------------

def test_weight_bytes_structural_hbm_win():
    """The acceptance accounting: bytes-of-weights-read per predict ~4x
    lower for int8 vs f32, ~8x for int4 (scale overhead keeps it just
    under the raw dtype ratios)."""
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Dense
    m = Sequential()
    m.add(Dense(512, activation="relu", input_shape=(1024,)))
    m.add(Dense(1024, activation="softmax"))
    m.init_weights()
    x = np.random.default_rng(0).standard_normal((8, 1024)).astype(
        np.float32)
    base = qz.weight_bytes(m._params)
    qp8 = qz.quantize(m, m._params, m._state, np.asarray(x))
    qp4 = qz.quantize_params(m, m._params, {}, bits=4, group_size=128)
    r8 = base / qz.weight_bytes(qp8)
    r4 = base / qz.weight_bytes(qp4)
    assert 3.5 <= r8 <= 4.0, r8
    assert 6.5 <= r4 <= 8.0, r4


# -- weight-store round-trip ---------------------------------------------------

def _roundtrip_model_builder():
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn.layers import Convolution2D, Dense, Flatten
    m = Sequential()
    m.add(Convolution2D(8, 3, activation="relu", border_mode="same",
                        input_shape=(8, 8, 3)))
    m.add(Flatten())
    m.add(Dense(32, activation="relu"))
    m.add(Dense(5, activation="softmax"))
    return m


@pytest.mark.parametrize("bits", [8, 4])
def test_weightstore_quantized_roundtrip(tmp_path, bits, rng):
    """save_store/load_store preserve int8/uint8-packed and f32-scale
    leaves bitwise, and do_load_store after do_quantize predicts
    IDENTICALLY to the in-memory quantized model."""
    m = _roundtrip_model_builder()
    m.init_weights()
    x = rng.standard_normal((16, 8, 8, 3)).astype(np.float32)
    im = InferenceModel().do_load_model(m, m._params, m._state)
    im.do_quantize(x if bits == 8 else None, force=True, bits=bits,
                   group_size=64)
    y_mem = im.do_predict(x)
    store = str(tmp_path / f"store{bits}")
    weightstore.save_store(store, {"params": im._params,
                                   "state": im._state or {}})
    # leaves round-trip bitwise at their quantized dtypes (manifest-checked)
    manifest = weightstore.read_manifest(store)
    flat_mem = {p: np.asarray(l) for p, l in qz._leaf_items(
        {"params": im._params, "state": im._state or {}})}
    flat_disk = weightstore.load_flat(store)
    assert set(flat_disk) == set(flat_mem)
    for key, a in flat_disk.items():
        assert manifest["leaves"][key]["dtype"] == np.dtype(a.dtype).str
        assert np.array_equal(a, flat_mem[key]), key
    wq_dtypes = {k.rsplit("/", 1)[-1]: np.dtype(v.dtype).str
                 for k, v in flat_disk.items()}
    assert wq_dtypes["W_q" if bits == 8 else "W_q4"] == \
        ("|i1" if bits == 8 else "|u1")
    # a FRESH process-shape restore (new auto-names) serves identically
    im_r = InferenceModel().do_load(_roundtrip_model_builder, store)
    assert im_r.load_mmap
    assert np.array_equal(im_r.do_predict(x), y_mem)
    assert qz.quantized_bits(im_r._params) == bits


def test_quantized_fallback_gated_to_quantized_stores(tmp_path, rng):
    """Review regression: the nested-restore fallback only engages for
    stores that actually hold quantized leaves — a FLOAT store that fails
    the keyed+positional match (wrong topology, truncation) keeps failing
    LOUDLY at load, never silently restoring into the wrong model."""
    m = _mlp_model(inp=16, out=8)
    store = str(tmp_path / "float_store")
    weightstore.save_store(store, {"params": m._params,
                                   "state": m._state or {}})

    def wrong_builder():
        from analytics_zoo_tpu.nn import Sequential
        from analytics_zoo_tpu.nn.layers import Dense
        w = Sequential()
        w.add(Dense(5, activation="softmax", input_shape=(16,)))
        return w

    with pytest.raises(KeyError):
        InferenceModel().do_load(wrong_builder, store)
    # a QUANTIZED store with mismatched shared leaves fails loudly too
    # (the remap verification covers identity mappings)
    imq = InferenceModel().do_load_model(m, m._params, m._state)
    imq.do_quantize(None, force=True, bits=4)
    qstore = str(tmp_path / "q_store")
    weightstore.save_store(qstore, {"params": imq._params,
                                    "state": imq._state or {}})
    with pytest.raises(KeyError):
        InferenceModel().do_load(wrong_builder, qstore)


def test_weightstore_natural_container_order():
    """Review regression: the positional container remap orders
    auto-name suffixes NUMERICALLY — plain lexicographic sort puts
    dense_10 before dense_8 and would cross-wire a remap at every
    power-of-10 suffix boundary."""
    dirs = [f"params/dense_{i}" for i in (8, 9, 10, 11)]
    assert sorted(dirs, key=weightstore._natural) == dirs
    assert sorted(dirs) != dirs              # the bug being guarded


def test_weightstore_manifest_dtype_check(tmp_path, rng):
    """A leaf file that drifted from its manifest entry fails loudly —
    quantized stores must never dequantize garbage."""
    m = _mlp_model()
    store = str(tmp_path / "store")
    weightstore.save_store(store, {"params": m._params,
                                   "state": m._state or {}})
    manifest = weightstore.read_manifest(store)
    first = sorted(manifest["leaves"])[0]
    path = os.path.join(store, manifest["leaves"][first]["file"])
    np.save(path, np.zeros((3, 3), np.int8), allow_pickle=False)
    with pytest.raises(ValueError, match="manifest"):
        weightstore.load_flat(store)


# -- manifest + sharding plan --------------------------------------------------

def test_manifest_quantized_variant(rng):
    m = _mlp_model(inp=16, out=8)
    im = InferenceModel(max_batch=4).do_load_model(m, m._params, m._state)
    assert {e.variant for e in aot.warmup_manifest(im)} == {"float"}
    x = rng.standard_normal((8, 16)).astype(np.float32)
    im.do_quantize(x, force=True, bits=8)
    entries = aot.warmup_manifest(im)
    assert {e.variant for e in entries} == {"w8"}
    # the rest of the golden derivation is unchanged by quantization
    assert sorted({e.bucket for e in entries}) == [1, 2, 4]
    im4 = InferenceModel(max_batch=4).do_load_model(m, m._params, m._state)
    im4.do_quantize(None, force=True, bits=4)
    assert {e.variant for e in aot.warmup_manifest(im4)} == {"w4"}


def test_sharding_plan_covers_quantized_leaves():
    """megatron_plan shards W_q/W_q4 exactly like the W they replace and
    puts each scale leaf on the axis its values are indexed by."""
    from jax.sharding import PartitionSpec as P

    from analytics_zoo_tpu.parallel.sharding import megatron_plan
    plan = megatron_plan()
    kn, g_n, n_, khalf_n = (64, 128), (2, 128), (128,), (32, 128)
    # column-parallel (qkv): out dim splits -> scales follow out
    assert plan.spec_for("blk/qkv/W", np.zeros(kn)) == P(None, "model")
    assert plan.spec_for("blk/qkv/W_q", np.zeros(kn)) == P(None, "model")
    assert plan.spec_for("blk/qkv/W_q4", np.zeros(khalf_n)) == \
        P(None, "model")
    assert plan.spec_for("blk/qkv/s_w", np.zeros(n_)) == P("model")
    assert plan.spec_for("blk/qkv/s_g", np.zeros(g_n)) == P(None, "model")
    # row-parallel (attn out): contraction splits -> s_w replicates,
    # groups ride the contraction axis
    assert plan.spec_for("blk/attn/out/W_q", np.zeros(kn)) == \
        P("model", None)
    assert plan.spec_for("blk/attn/out/W_q4", np.zeros(khalf_n)) == \
        P("model", None)
    assert plan.spec_for("blk/attn/out/s_w", np.zeros(n_)) == P()
    assert plan.spec_for("blk/attn/out/s_g", np.zeros(g_n)) == \
        P("model", None)


# -- serving config surface ----------------------------------------------------

def test_resolve_quantize_spec_forms():
    from analytics_zoo_tpu.serving.engine import resolve_quantize_spec
    assert resolve_quantize_spec(None) is None
    assert resolve_quantize_spec(False) is None
    assert resolve_quantize_spec("int4")["bits"] == 4
    assert resolve_quantize_spec(8)["bits"] == 8
    spec = resolve_quantize_spec({"bits": 4, "group_size": 128,
                                  "percentile": 99.9})
    assert spec == {"bits": 4, "group_size": 128, "percentile": 99.9,
                    "calib": None}
    with pytest.raises(ValueError):
        resolve_quantize_spec("int2")
    with pytest.raises(ValueError):
        resolve_quantize_spec({"bits": 16})


def test_engine_quantizes_at_construction(tmp_path, rng):
    """ServingParams.quantize: int4 quantizes the model before sharding;
    int8 without calibration fails construction loudly; int8 with a calib
    file quantizes using its activation scales."""
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue

    m = _mlp_model()
    im = InferenceModel(max_batch=4).do_load_model(m, m._params, m._state)
    s = ClusterServing(im, InProcQueue(),
                       params=ServingParams(quantize="int4"))
    assert qz.quantized_bits(im._params) == 4
    assert s.health()["quantized_bits"] == 4

    im8 = InferenceModel(max_batch=4).do_load_model(m, m._params, m._state)
    with pytest.raises(ValueError, match="calib"):
        ClusterServing(im8, InProcQueue(),
                       params=ServingParams(quantize="int8"))
    calib = str(tmp_path / "calib.npy")
    np.save(calib, rng.standard_normal((32, 16)).astype(np.float32))
    s8 = ClusterServing(im8, InProcQueue(), params=ServingParams(
        quantize={"bits": 8, "calib": calib}))
    assert qz.quantized_bits(im8._params) == 8
    assert s8.health()["quantized_bits"] == 8
    # already-quantized models are never re-quantized (a restored
    # quantized store must not stack quantization error)
    before = {p: np.asarray(l)
              for p, l in qz._leaf_items(im8._params)}
    ClusterServing(im8, InProcQueue(), params=ServingParams(
        quantize={"bits": 8, "calib": calib}))
    after = {p: np.asarray(l) for p, l in qz._leaf_items(im8._params)}
    assert all(np.array_equal(before[k], after[k]) for k in before)


# -- warm quantized serving: zero steady-state compiles ------------------------

def test_warm_quantized_predict_zero_compiles(rng):
    """The acceptance contract (same as PRs 11/12): after warm-up, a
    quantized deployment serves every bucket it can hit with ZERO further
    XLA compiles — COMPILE_STATS-asserted."""
    aot.install_compile_listeners()
    m = _mlp_model(inp=16, out=8)
    im = InferenceModel(max_batch=8).do_load_model(m, m._params, m._state)
    im.do_quantize(None, force=True, bits=4, group_size=64)
    entries = aot.warmup_manifest(im)
    assert {e.variant for e in entries} == {"w4"}
    stats = aot.warm_up(im, entries)
    assert stats["failed"] == 0
    compiles = im.aot_stats()["compiles"]
    before = aot.COMPILE_STATS.snapshot()
    for n in (1, 2, 3, 5, 8):
        im.do_predict(rng.standard_normal((n, 16)).astype(np.float32))
        im.dispatch(rng.standard_normal((n, 16)).astype(
            np.float32)).result()
        im.do_predict((rng.standard_normal((n, 16)) * 10).astype(np.int8),
                      scales=np.ones(n, np.float32))
    after = aot.COMPILE_STATS.snapshot()
    assert im.aot_stats()["compiles"] == compiles, \
        "a warmed quantized bucket was re-compiled"
    assert after["compile_requests"] == before["compile_requests"]


def test_engine_warm_quantized_serving(rng):
    """Engine e2e: quantize via config + warm-up thread -> readiness ->
    records served off the warmed quantized executables with zero further
    compiles, results close to the float engine's."""
    import time

    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.engine import ClusterServing, ServingParams
    from analytics_zoo_tpu.serving.queues import InProcQueue

    m = _mlp_model(inp=16, out=8)
    x = rng.standard_normal((4, 16)).astype(np.float32)

    im = InferenceModel(max_batch=8).do_load_model(m, m._params, m._state)
    q = InProcQueue()
    s = ClusterServing(im, q, params=ServingParams(
        batch_size=4, quantize={"bits": 4, "group_size": 64},
        warmup=True))
    # the serving contract: records come back EXACTLY as the in-memory
    # quantized model predicts them (accuracy-vs-float is the goldens'
    # job; this engine is already quantized by construction)
    y_q = im.do_predict(x)
    s.start()
    try:
        deadline = time.time() + 60
        while s.warmup_state()["state"] in ("pending", "warming"):
            assert time.time() < deadline, "warm-up never completed"
            time.sleep(0.05)
        assert s.warmup_state()["state"] == "ready"
        compiles = im.aot_stats()["compiles"]
        cin, cout = InputQueue(q), OutputQueue(q)
        uris = [cin.enqueue_tensor(f"r{i}", x[i]) for i in range(4)]
        res = cout.query_many(uris, timeout_s=30)
        assert all(r is not None and not OutputQueue.is_error(r)
                   for r in res.values())
        assert im.aot_stats()["compiles"] == compiles, \
            "warm quantized serving compiled mid-stream"
        assert s.health()["quantized_bits"] == 4
        # served top-1 == the in-memory quantized model's top-1
        for i, uri in enumerate(uris):
            top = res[uri]["value"][0][0]
            assert int(top) == int(y_q[i].argmax())
    finally:
        s.shutdown()


# -- bench tier-1 smoke --------------------------------------------------------

def test_bench_quantize_smoke(tmp_path):
    """serving_bench --smoke --quantize: the A/B completes inside tier-1,
    reports throughput AND accuracy side by side, and asserts zero
    steady-state compiles on the quantized side itself."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import serving_bench
    out = serving_bench.main(["--smoke", "--quantize", "int4",
                              "--json", str(tmp_path / "q.json")])
    assert out["mode"] == "quantize-ab" and out["bits"] == 4
    assert out["steady_compiles_quantized"] == 0
    assert out["top1_agreement"] >= 0.9
    assert out["weight_bytes_ratio"] > 2.0
    doc = json.loads((tmp_path / "q.json").read_text())
    assert doc["results"][0]["quantize"] == "int4"


# -- manager warmup exports the quantized store --------------------------------

def test_manager_warmup_quantized_store(tmp_path, capsys):
    """`manager warmup` with params.quantize: the pass quantizes BEFORE
    exporting, so the per-deployment mmap store holds packed int4 + scale
    leaves and a replica boot serves quantized from it."""
    from analytics_zoo_tpu.serving import manager

    topo = tmp_path / "topology.py"
    topo.write_text(
        "from analytics_zoo_tpu.nn import Sequential\n"
        "from analytics_zoo_tpu.nn.layers import Dense\n"
        "def build_model():\n"
        "    m = Sequential()\n"
        "    m.add(Dense(8, activation='softmax', input_shape=(16,)))\n"
        "    return m\n")
    m = _mlp_model(inp=16, out=8)
    weights = str(tmp_path / "weights.npz")
    m.save_weights(weights)
    # pre-seed the per-deployment store with the FLOAT tree (in production
    # the npz restores keyed — in this test process, layer auto-name
    # suffixes have drifted, which the store's positional fallback
    # handles and the npz's keyed loader does not)
    pidfile = str(tmp_path / "serve.pid")
    weightstore.save_store(pidfile + ".weights",
                           {"params": m._params, "state": m._state or {}})
    cfg = tmp_path / "config.yaml"
    cfg.write_text(
        "model:\n"
        f"  path: {weights}\n"
        f"  topology: {topo}\n"
        "params:\n"
        "  quantize: int4\n"
        "  warmup: true\n"
        "  compile_cache_dir: off\n")
    rc = manager.main(["warmup", "-c", str(cfg), "--pidfile", pidfile])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["quantized_bits"] == 4
    assert out["failed"] == 0 and out["store_exported"]
    store = pidfile + ".weights"
    assert weightstore.is_store(store)
    dtypes = {k.rsplit("/", 1)[-1]: v["dtype"]
              for k, v in weightstore.read_manifest(store)["leaves"].items()}
    assert dtypes["W_q4"] == "|u1" and dtypes["s_g"] == "<f4"
    # the replica-boot path restores the QUANTIZED tree from the store
    cfg_dict = manager.load_config(str(cfg))
    im = manager.load_model(cfg_dict, weight_store=store)
    assert im.load_mmap
    assert qz.quantized_bits(im._params) == 4
    # ...and construction-time quantize is a no-op on it (already packed)
    from analytics_zoo_tpu.serving.engine import apply_quantize
    assert apply_quantize(im, "int4") is False
