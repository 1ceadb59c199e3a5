"""Fused-dequant quantized matmul — int8 / int4 weight kernels (PR 14).

Serving predict for the memory-bound models (bert decode, wide MLP heads)
is dominated by weight HBM traffic, not FLOPs: every f32 weight byte read
per token is bandwidth the MXU waits on.  The reference platform's answer
is OpenVINO int8-with-VNNI (OpenVinoInferenceSupportive.scala: calibrate ->
quantize -> serve); the TPU-native finish line implemented here keeps the
weights COMPACT in HBM and dequantizes per-tile in VMEM, fused into the
MXU matmul:

- ``w8a8_matmul``: s8 x s8 -> s32 accumulation on the MXU, dequantized by
  the combined ``s_x * s_w`` scale on the OUTPUT tile — 4x less weight HBM
  than f32, and the int32 accumulation is exact, so the Pallas kernel is
  BITWISE-equal to the XLA reference (the parity tests assert it).
- ``w4a16_matmul``: weights nibble-packed two-per-byte (8x less weight
  HBM), per-GROUP scales along the contraction axis; the kernel unpacks
  and dequantizes one group tile at a time in VMEM and accumulates in f32
  — activations stay 16/32-bit (weight-only quantization, the usual
  int4 recipe).

Every kernel ships with a pure-XLA reference implementation: what a CPU
process computes through (``impl="auto"`` resolves by backend in
``ops/dispatch.resolve_impl``) and the numerics ORACLE the parity tests
compare against.

Block sizes follow the flash_attention precedent: (128, 128) output tiles
keep every dot MXU-shaped; the w4 group loop runs ``group_size``-row
K-blocks (group_size=128 default, so the dequant tiles are MXU-shaped
too).  The contraction axis stays VMEM-resident per output tile — the same
layout flash_attention uses for K/V — which bounds the practical K around
~64k at these tile widths; serving layer widths sit far below that.

int4 packing is SPLIT ("planar"): byte row j carries weight row j in the
low nibble and weight row j + ceil(K/2) in the high nibble, so the kernel
unpacks each half with one mask/shift and runs two clean MXU dots instead
of interleaving rows in-register.  ``pack_int4``/``unpack_int4`` are the
one packing contract shared by the quantizer, the kernels, and the
weight store.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from analytics_zoo_tpu.ops.dispatch import resolve_impl

# Output-tile block sizes (MXU-shaped; clamped to the padded operand).
BLOCK_M = 128
BLOCK_N = 128
# int8 operands need >= 32 sublanes per tile, f32 >= 8 (Mosaic tiling).
_SUBLANE_I8 = 32
_SUBLANE_F32 = 8
_LANE = 128

# Default quantization group along the contraction axis for int4 weights:
# one scale per (group, out-channel).  128 keeps the in-kernel dequant
# tiles MXU-shaped AND the scale overhead at K*N/64 bytes (f32 scale per
# 128 nibbles).
W4_GROUP = 128


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // int(m)) * int(m)


# -- int4 packing (two weights per byte, split layout) -------------------------

def pack_int4(q) -> np.ndarray:
    """Pack int4 values ``q`` (K, N) in [-8, 7] into (ceil(K/2), N) uint8:
    byte row j = row j (low nibble) | row j + ceil(K/2) (high nibble).
    Odd K pads the high half's last row with zero nibbles (decoded as
    weight 0)."""
    q = np.asarray(q)
    if q.ndim != 2:
        raise ValueError(f"pack_int4 expects (K, N), got {q.shape}")
    k = q.shape[0]
    k_half = (k + 1) // 2
    lo = q[:k_half].astype(np.uint8) & 0xF
    hi = np.zeros_like(lo)
    hi[: k - k_half] = q[k_half:].astype(np.uint8) & 0xF
    return (lo | (hi << 4)).astype(np.uint8)


def unpack_int4(packed, k: int):
    """Inverse of :func:`pack_int4`: (ceil(K/2), N) uint8 -> (K, N) int8
    (jnp — usable inside jitted programs)."""
    b = jnp.asarray(packed).astype(jnp.int32)
    lo = ((b & 0xF) ^ 8) - 8
    hi = ((b >> 4) ^ 8) - 8
    k_half = (int(k) + 1) // 2
    return jnp.concatenate([lo[:k_half], hi[: int(k) - k_half]],
                           axis=0).astype(jnp.int8)


def expand_group_scales(s_g, k: int):
    """Per-group scales (G, N) -> per-row scales (K, N): group g covers
    contraction rows [g*gs, (g+1)*gs) with gs = ceil(K/G) (the effective
    group size the quantizer normalized to — derivable from shapes alone,
    so jitted callers need no side-channel group-size leaf)."""
    g = int(s_g.shape[0])
    gs = -(-int(k) // g)
    return jnp.repeat(jnp.asarray(s_g), gs, axis=0)[: int(k)]


# -- XLA reference implementations (the CPU path + numerics oracle) -----------

def w8a8_matmul_xla(x_q, w_q, scale):
    """``x_q`` (M, K) int8 @ ``w_q`` (K, N) int8 with int32 accumulation,
    dequantized by ``scale`` (N,) f32 (= s_x * s_w, combined by the
    caller).  The oracle: the Pallas kernel computes the identical
    expression, so outputs match bitwise."""
    acc = jax.lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


def w4a16_matmul_xla(x, w_q4, s_g):
    """Weight-only int4 reference: unpack nibbles, dequantize with the
    per-group scales, matmul in f32.  K is taken from ``x``."""
    k = int(x.shape[-1])
    w = unpack_int4(w_q4, k).astype(jnp.float32) * expand_group_scales(s_g, k)
    return jnp.matmul(x.astype(jnp.float32), w,
                      preferred_element_type=jnp.float32)


# -- Pallas kernels ------------------------------------------------------------

def _w8a8_kernel(x_ref, w_ref, s_ref, o_ref):
    # x: (bm, K) s8; w: (K, bn) s8; s: (1, bn) f32; o: (bm, bn) f32.
    # One MXU dot with s32 accumulation; dequant fused on the output tile
    # (the only place the f32 ever materializes).
    acc = jax.lax.dot_general(x_ref[...], w_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    o_ref[...] = acc.astype(jnp.float32) * s_ref[...]


def w8a8_matmul_pallas(x_q, w_q, scale, block_m: int = BLOCK_M,
                       block_n: int = BLOCK_N, interpret: bool = False):
    """Blockwise fused-dequant int8 matmul: grid over (M, N) output tiles,
    weights stay int8 in HBM and stream through VMEM one (K, bn) tile per
    program — 1/4 the weight bytes of the f32 path."""
    m, k = int(x_q.shape[0]), int(x_q.shape[1])
    n = int(w_q.shape[1])
    bm = min(int(block_m), _round_up(max(m, 1), _SUBLANE_I8))
    bn = min(int(block_n), _round_up(max(n, 1), _LANE))
    m_pad, n_pad = _round_up(m, bm), _round_up(n, bn)
    k_pad = _round_up(k, _LANE)
    if m_pad != m or k_pad != k:
        x_q = jnp.pad(x_q, [(0, m_pad - m), (0, k_pad - k)])
    if n_pad != n or k_pad != k:
        w_q = jnp.pad(w_q, [(0, k_pad - k), (0, n_pad - n)])
    s2 = jnp.asarray(scale, jnp.float32).reshape(1, n)
    if n_pad != n:
        s2 = jnp.pad(s2, [(0, 0), (0, n_pad - n)])
    out = pl.pallas_call(
        _w8a8_kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        grid=(m_pad // bm, n_pad // bn),
        in_specs=[
            pl.BlockSpec((bm, k_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((k_pad, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        name="w8a8_matmul",
    )(x_q, w_q, s2)
    return out[:m, :n]


def _w4_chunk(gs: int) -> int:
    """Contraction rows one kernel loop step covers: whole groups AND whole
    128-lane tiles, because the step slices the activation's LANE axis at
    a dynamic offset and Mosaic only takes lane offsets it can prove are
    multiples of 128 (group_size 64 — the quantizer's default — sliced at
    64 did not compile)."""
    return gs * _LANE // math.gcd(gs, _LANE)


def _w4a16_kernel(x_ref, p_ref, s_ref, o_ref, *, k: int, gs: int,
                  n_groups: int):
    # x: (bm, K) f32/bf16; p: (K//2, bn) u8 split-packed; s: (G, bn) f32;
    # o: (bm, bn) f32.  Loop over lane-aligned K-chunks of whole groups:
    # each packed tile yields TWO weight tiles (low nibble = contraction
    # rows [j*chunk, ..), high nibble = the same rows offset by K//2), each
    # dequantized by its groups' scale rows entirely in VMEM and fed to
    # the MXU as one chunk-deep dot.
    half = k // 2
    g_half = n_groups // 2
    chunk = _w4_chunk(gs)
    per = chunk // gs                     # groups per chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def scales(g0):
        # (chunk, bn): row i scales by group g0 + i // gs
        out = s_ref[pl.ds(g0, 1), :]
        for g in range(1, per):
            out = jnp.where(row >= g * gs, s_ref[pl.ds(g0 + g, 1), :], out)
        return out

    def body(j, acc):
        off = pl.multiple_of(j * chunk, chunk)
        b = p_ref[pl.ds(off, chunk), :].astype(jnp.int32)
        w_lo = (((b & 0xF) ^ 8) - 8).astype(jnp.float32) * scales(j * per)
        w_hi = (((b >> 4) ^ 8) - 8).astype(jnp.float32) \
            * scales(g_half + j * per)
        x_lo = x_ref[:, pl.ds(off, chunk)].astype(jnp.float32)
        x_hi = x_ref[:, pl.ds(pl.multiple_of(half + off, _LANE), chunk)] \
            .astype(jnp.float32)
        acc = acc + jax.lax.dot_general(
            x_lo, w_lo, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + jax.lax.dot_general(
            x_hi, w_hi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc

    acc0 = jnp.zeros(o_ref.shape, jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, half // chunk, body, acc0)


def _w4_pallas_ok(k: int, n_groups: int) -> bool:
    """The kernel's alignment contract: groups divide K EXACTLY (the
    kernel's ``gs = k // n_groups`` must equal the expansion's
    ``ceil(k/n_groups)`` — a ragged division would mis-slice packed and
    scale rows silently), even K, group rows a legal uint8 sublane tile,
    halves made of whole lane-aligned chunks (``_w4_chunk``).
    ``impl="auto"`` sends shapes outside it to the XLA reference; an
    explicit kernel request raises."""
    if k <= 0 or k % 2 != 0 or n_groups % 2 != 0 or k % n_groups != 0:
        return False
    gs = k // n_groups
    return gs % _SUBLANE_I8 == 0 and (k // 2) % _w4_chunk(gs) == 0


def w4a16_matmul_pallas(x, w_q4, s_g, block_m: int = BLOCK_M,
                        block_n: int = BLOCK_N, interpret: bool = False):
    m, k = int(x.shape[0]), int(x.shape[1])
    n = int(w_q4.shape[1])
    n_groups = int(s_g.shape[0])
    if not _w4_pallas_ok(k, n_groups):
        raise ValueError(
            f"w4a16 kernel needs even K with whole {_SUBLANE_I8}-aligned "
            f"groups in {_LANE}-aligned chunks per half (K={k}, "
            f"groups={n_groups}); use the XLA reference for this shape")
    gs = k // n_groups
    bm = min(int(block_m), _round_up(max(m, 1), _SUBLANE_F32))
    bn = min(int(block_n), _round_up(max(n, 1), _LANE))
    m_pad, n_pad = _round_up(m, bm), _round_up(n, bn)
    if m_pad != m:
        x = jnp.pad(x, [(0, m_pad - m), (0, 0)])
    if n_pad != n:
        w_q4 = jnp.pad(w_q4, [(0, 0), (0, n_pad - n)])
        s_g = jnp.pad(jnp.asarray(s_g), [(0, 0), (0, n_pad - n)])
    out = pl.pallas_call(
        functools.partial(_w4a16_kernel, k=k, gs=gs, n_groups=n_groups),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        grid=(m_pad // bm, n_pad // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k // 2, bn), lambda i, j: (0, j)),
            pl.BlockSpec((n_groups, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        name="w4a16_matmul",
    )(x, w_q4, jnp.asarray(s_g, jnp.float32))
    return out[:m, :n]


# -- public entry points -------------------------------------------------------

def w8a8_matmul(x_q, w_q, scale, impl: Optional[str] = None):
    """Fused-dequant int8 matmul: (M, K) s8 @ (K, N) s8 -> (M, N) f32
    ``= (x_q @ w_q).astype(f32) * scale``.  ``impl`` auto-selects the
    Pallas kernel on TPU, the XLA reference on CPU."""
    mode = resolve_impl(impl)
    if mode == "xla":
        return w8a8_matmul_xla(x_q, w_q, scale)
    return w8a8_matmul_pallas(x_q, w_q, scale,
                              interpret=(mode == "interpret"))


def w4a16_matmul(x, w_q4, s_g, impl: Optional[str] = None):
    """Weight-only int4 matmul: (M, K) f32/bf16 @ nibble-packed
    (ceil(K/2), N) u8 with per-group scales (G, N) -> (M, N) f32.  With
    ``impl`` auto, a shape outside the kernel's alignment contract
    (``_w4_pallas_ok`` — a property of the operands, visible to the
    caller) computes through the XLA reference; asking for ``pallas`` or
    ``interpret`` on such a shape raises instead of being demoted."""
    mode = resolve_impl(impl)
    if impl in (None, "auto") and not _w4_pallas_ok(
            int(x.shape[-1]), int(s_g.shape[0])):
        mode = "xla"
    if mode == "xla":
        return w4a16_matmul_xla(x, w_q4, s_g)
    return w4a16_matmul_pallas(x, w_q4, s_g,
                               interpret=(mode == "interpret"))


def _flatten_batch(x):
    """(..., K) -> ((M, K), unflatten) for the 2-D kernels."""
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= int(d)
    x2 = x.reshape((m, x.shape[-1]))
    return x2, lambda y: y.reshape(lead + (y.shape[-1],))


def w8a8_dense(x_q, w_q, scale, impl: Optional[str] = None):
    """Dense-layer entry: any-rank activations (..., K) s8 against
    (K, N) s8 weights, output (..., N) f32 dequantized by ``scale``."""
    x2, unflat = _flatten_batch(x_q)
    return unflat(w8a8_matmul(x2, w_q, scale, impl=impl))


def w4a16_dense(x, w_q4, s_g, impl: Optional[str] = None):
    x2, unflat = _flatten_batch(x)
    return unflat(w4a16_matmul(x2, w_q4, s_g, impl=impl))


def _is_pointwise(kshape: Sequence[int], strides, dilation,
                  groups: int, padding) -> bool:
    """A conv is a pure channel matmul only when its spatial geometry is
    the identity — 1x1 kernel, stride/dilation 1, dense groups AND no
    spatial padding.  For a 1x1 kernel SAME == VALID == zero pad, but
    caffe-style explicit padding ([(1, 1), ...]) grows the output and
    must stay on the real conv path."""
    spatial = tuple(int(s) for s in kshape[:-2])
    if isinstance(padding, str):
        pad_free = padding.upper() in ("SAME", "VALID")
    else:
        pad_free = all(int(lo) == 0 and int(hi) == 0
                       for lo, hi in padding)
    return (pad_free
            and all(s == 1 for s in spatial)
            and all(int(s) == 1 for s in strides)
            and all(int(d) == 1 for d in dilation)
            and int(groups) == 1)


def w8a8_conv(x_q, w_q, scale, *, window_strides, padding, rhs_dilation,
              dimension_numbers, feature_group_count: int = 1,
              impl: Optional[str] = None):
    """Fused-dequant int8 convolution.  A pointwise (1x1, stride 1,
    dense-groups) conv IS a channel matmul and routes through the blockwise
    kernel; spatial convs run the s8 x s8 -> s32 XLA conv with the same
    output-side dequant (XLA fuses the elementwise scale).  ``x_q`` is
    NHWC-ish (batch, *spatial, cin), ``w_q`` (*spatial, cin/g, cout)."""
    kshape = tuple(int(s) for s in w_q.shape)
    if _is_pointwise(kshape, window_strides, rhs_dilation,
                     feature_group_count, padding):
        x2, unflat = _flatten_batch(x_q)
        w2 = w_q.reshape((kshape[-2], kshape[-1]))
        return unflat(w8a8_matmul(x2, w2, scale, impl=impl))
    acc = jax.lax.conv_general_dilated(
        x_q, w_q, window_strides=window_strides, padding=padding,
        rhs_dilation=rhs_dilation, dimension_numbers=dimension_numbers,
        feature_group_count=feature_group_count,
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


def w4a16_conv(x, w_q4, s_g, kshape: Tuple[int, ...], *, window_strides,
               padding, rhs_dilation, dimension_numbers,
               feature_group_count: int = 1, impl: Optional[str] = None):
    """Weight-only int4 convolution: the kernel tensor lives nibble-packed
    as (ceil(K/2), cout) with K = prod(spatial) * cin/g.  Pointwise convs
    route through the fused matmul kernel; spatial convs unpack +
    dequantize group-wise (XLA fuses it into the conv's weight read) and
    convolve in f32."""
    kshape = tuple(int(s) for s in kshape)
    k = 1
    for d in kshape[:-1]:
        k *= d
    if _is_pointwise(kshape, window_strides, rhs_dilation,
                     feature_group_count, padding):
        x2, unflat = _flatten_batch(x)
        return unflat(w4a16_matmul(x2, w_q4, s_g, impl=impl))
    w = unpack_int4(w_q4, k).astype(jnp.float32) \
        * expand_group_scales(s_g, k)
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.reshape(kshape),
        window_strides=window_strides, padding=padding,
        rhs_dilation=rhs_dilation, dimension_numbers=dimension_numbers,
        feature_group_count=feature_group_count,
        preferred_element_type=jnp.float32)
