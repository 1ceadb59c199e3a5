"""Paged decode attention — block-pool KV gather kernels (PR 18).

The continuous batcher's monolithic per-slot KV lanes become a fixed pool
of ``(n_blocks, block_len, heads * head_dim)`` buffers; each decode row
owns a BLOCK TABLE mapping its logical cache blocks to physical pool
blocks (the vLLM paged-attention layout).  This module is the read side:
one query token per row attends over the row's table-mapped blocks.

Pool layout: heads and head_dim are FOLDED into one trailing axis, so a
pool block is a (block_len, heads * head_dim) tile whose lane dimension is
the model width (768 at GPT-2-small) — the shape Mosaic tiles natively.
The unfolded (block_len, heads, head_dim) form put head_dim (64) in the
lanes and heads (12) in the sublanes: neither divides the (8, 128) vreg
tile, every block padded 2.7x in HBM, and the kernel did not lower.

Two data paths, the `quant_matmul.py` shape:

- ``paged_attention_xla`` — pure-XLA reference: gather the table's blocks,
  dequantize (int8 mode), re-linearize to the monolithic cache layout and
  run EXACTLY the einsum+mask+softmax ``TransformerLM.decode_step`` runs.
  Because the gather materializes the same values at the same positions,
  the float path is BITWISE-equal to monolithic decode on one backend —
  the parity anchor, and what a CPU process serves through.
- ``_paged_kernel`` — Pallas TPU kernel: the block table rides in as a
  SCALAR-PREFETCH operand (``pltpu.PrefetchScalarGridSpec``) so the
  ``k_pool``/``v_pool`` BlockSpec index maps dereference it per grid step
  — the pool block streams HBM->VMEM by PHYSICAL id, no host gather, no
  (A, used_len) materialization.  Online-softmax carry across the
  page-grid axis, flash_attention style.  Per-head reductions over the
  folded lane axis are matmuls against a 0/1 head-membership matrix
  (``_head_segments``), so every in-kernel value is a plain 2-D tile.
  int8 pools dequantize IN-KERNEL: the per-(block, head) scale multiplies
  the per-head logits / probabilities, never the (block_len, width) tile.

``impl`` resolves through ``ops/dispatch.resolve_impl``: Pallas on a TPU
backend, the reference on CPU; ``"interpret"`` runs the kernel on CPU for
the parity tests.

Quantization contract: ``inference/quantize.kv_pack_int8`` /
``kv_unpack_int8`` (symmetric, scale = per-(block, head) absmax / 127) —
the ONE contract shared with the decode append path and the prefill
commit program.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.inference.quantize import kv_unpack_int8
from analytics_zoo_tpu.ops.dispatch import resolve_impl

NEG_INF = -1e30
_LANE = 128
_SUBLANE = 8


def _check(q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale):
    if q.ndim != 3:
        raise ValueError(f"q must be (rows, heads, head_dim), got {q.shape}")
    if k_pool.ndim != 3 or v_pool.shape != k_pool.shape \
            or k_pool.shape[2] != q.shape[1] * q.shape[2]:
        raise ValueError(
            f"pools must be matching (n_blocks, block_len, heads * "
            f"head_dim = {q.shape[1] * q.shape[2]}), got {k_pool.shape} / "
            f"{v_pool.shape}")
    if block_tables.ndim != 2 or block_tables.shape[0] != q.shape[0]:
        raise ValueError(
            f"block_tables must be (rows, n_table), got "
            f"{block_tables.shape} for {q.shape[0]} rows")
    if lengths.shape != (q.shape[0],):
        raise ValueError(
            f"lengths must be (rows,), got {lengths.shape}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must be given together")
    if k_scale is not None and k_scale.shape != (k_pool.shape[0],
                                                 q.shape[1]):
        raise ValueError(
            f"scales must be (n_blocks, heads), got {k_scale.shape} "
            f"for pool {k_pool.shape}")


def _gather_dequant(pool, scale, block_tables, nh: int):
    """(A, n_table, block_len, heads, head_dim) f32 — the table's blocks
    in logical order, unfolded, dequantized when the pool is int8."""
    blocks = jnp.take(pool, block_tables, axis=0)
    blocks = blocks.reshape(blocks.shape[:-1] + (nh, -1))
    if scale is not None:
        blocks = kv_unpack_int8(blocks, jnp.take(scale, block_tables,
                                                 axis=0))
    return blocks.astype(jnp.float32)


def paged_attention_xla(q, k_pool, v_pool, block_tables, lengths,
                        k_scale=None, v_scale=None):
    """Reference path: gather -> dequant -> the exact decode_step
    attention (same einsums, same -1e30 mask, same softmax), so the float
    path is bitwise-identical to attending over a monolithic cache that
    holds the same values."""
    nh = q.shape[1]
    kc = _gather_dequant(k_pool, k_scale, block_tables, nh)
    vc = _gather_dequant(v_pool, v_scale, block_tables, nh)
    A, T, bl, nh, hd = kc.shape
    kc = kc.reshape(A, T * bl, nh, hd)
    vc = vc.reshape(A, T * bl, nh, hd)
    scale = 1.0 / np.sqrt(hd)
    att = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kc) * scale
    valid = jnp.arange(T * bl)[None] < lengths[:, None]         # (A, T*bl)
    att = jnp.where(valid[:, None], att, NEG_INF)
    att = jax.nn.softmax(att, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", att, vc)


def _head_segments(nh: int, hd: int):
    """0/1 head-membership matrix (heads * head_dim, heads padded to a
    lane multiple): column h is 1 on head h's lanes.  ``x @ seg`` sums
    each head's lanes (per-head reduce), ``y @ seg.T`` broadcasts a
    per-head value back over them — the folded-lane substitute for a
    (.., heads, head_dim) reshape, which Mosaic cannot do in registers."""
    seg = np.zeros((nh * hd, -(-nh // _LANE) * _LANE), np.float32)
    seg[np.arange(nh * hd), np.arange(nh * hd) // hd] = 1.0
    return seg


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  seg_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_len: int, n_table: int, scale: float):
    """One (row, table-entry) grid step: fold the prefetched block into
    the row's online-softmax carry (m/l per head, acc per lane; scratch
    persists across the table axis), emit at the last entry.  W = heads *
    head_dim lanes, P = heads padded to a lane multiple."""
    del bt_ref                            # consumed by the index maps
    a, t = pl.program_id(0), pl.program_id(1)
    exact = jax.lax.Precision.HIGHEST     # the 0/1 matmuls must not round

    def spread(rows):
        # (r, P) per-head values -> (r, W) over each head's lanes: rows @
        # seg.T, contracted on seg's head axis
        return jax.lax.dot_general(
            rows, seg_ref[...], (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # entries wholly past the row's length contribute nothing: skip them
    @pl.when(t * block_len < len_ref[a])
    def _fold():
        q = q_ref[0].astype(jnp.float32) * scale                  # (1, W)
        k = k_ref[0].astype(jnp.float32)                          # (bl, W)
        v = v_ref[0].astype(jnp.float32)
        # s[j, h] = ks[h] * sum_{lanes of h} q * k[j]
        s = jnp.dot(q * k, seg_ref[...], precision=exact,
                    preferred_element_type=jnp.float32) * ks_ref[0]
        idx = t * block_len + jax.lax.broadcasted_iota(
            jnp.int32, (block_len, 1), 0)
        s = jnp.where(idx < len_ref[a], s, NEG_INF)               # (bl, P)
        m_prev = m_ref[...]                                       # (8, P)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new[:1])                                # (bl, P)
        alpha = jnp.exp(m_prev - m_new)                           # (8, P)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        # one spread for both per-head factors: the (dequantized)
        # probabilities and the carry rescale
        wide = spread(jnp.concatenate([p * vs_ref[0], alpha], axis=0))
        acc_ref[...] = acc_ref[...] * wide[block_len:block_len + 1] \
            + jnp.sum(wide[:block_len] * v, axis=0, keepdims=True)

    @pl.when(t == n_table - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / spread(l_ref[...])[:1]).astype(
            o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, block_tables, lengths, k_scale,
                  v_scale, interpret: bool):
    A, nh, hd = q.shape
    n_blocks, bl, W = k_pool.shape
    n_table = int(block_tables.shape[1])
    seg = _head_segments(nh, hd)
    P = seg.shape[1]
    if k_scale is None:
        # one kernel for both modes: float pools ride unit scales
        # (x * 1.0 is exact, so the float kernel numerics are unchanged)
        k_scale = jnp.ones((n_blocks, nh), jnp.float32)
        v_scale = k_scale

    def lanes(s):
        # (n_blocks, heads) -> (n_blocks, 1, P): a block is one full
        # (1, P) row, and padded heads scale by 0 (they own no lanes)
        return jnp.pad(jnp.asarray(s, jnp.float32),
                       [(0, 0), (0, P - nh)])[:, None, :]

    def pool_block(a, t, bt, ln):
        return (bt[a, t], 0, 0)

    def row_block(a, t, bt, ln):
        return (a, 0, 0)

    def whole(a, t, bt, ln):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(A, n_table),
        in_specs=[
            pl.BlockSpec((1, 1, W), row_block),
            pl.BlockSpec((1, bl, W), pool_block),
            pl.BlockSpec((1, bl, W), pool_block),
            pl.BlockSpec((1, 1, P), pool_block),
            pl.BlockSpec((1, 1, P), pool_block),
            pl.BlockSpec((W, P), whole),
        ],
        out_specs=pl.BlockSpec((1, 1, W), row_block),
        # m/l carry 8 identical sublanes so every matmul operand built
        # from them is a whole (8, 128) tile
        scratch_shapes=[pltpu.VMEM((_SUBLANE, P), jnp.float32),
                        pltpu.VMEM((_SUBLANE, P), jnp.float32),
                        pltpu.VMEM((1, W), jnp.float32)])
    kernel = functools.partial(_paged_kernel, block_len=bl,
                               n_table=n_table, scale=1.0 / np.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, 1, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_int8" if k_pool.dtype == jnp.int8
        else "paged_attention",
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32), q.reshape(A, 1, W), k_pool, v_pool,
      lanes(k_scale), lanes(v_scale), seg)
    return out.reshape(A, nh, hd)


def paged_attention(q, k_pool, v_pool, block_tables, lengths,
                    k_scale=None, v_scale=None,
                    impl: Optional[str] = None):
    """One decode token per row over a paged KV pool.

    - ``q`` (rows, heads, head_dim) f32 — the current token's queries.
    - ``k_pool``/``v_pool`` (n_blocks, block_len, heads * head_dim) — f32,
      or int8 with ``k_scale``/``v_scale`` (n_blocks, heads) f32.
    - ``block_tables`` (rows, n_table) int32 — logical block j of row a
      lives in pool block ``block_tables[a, j]``.  Entries past a row's
      allocation may point anywhere resident (conventionally block 0, the
      batcher's trash block): their positions are masked by ``lengths``.
    - ``lengths`` (rows,) int32 >= 1 — valid cache positions per row
      (cursor + 1 at decode time: the current token's K/V is written
      before the read).

    Returns (rows, heads, head_dim) f32.  ``impl``: auto | pallas | xla |
    interpret (see ``ops/dispatch.resolve_impl``)."""
    q = jnp.asarray(q)
    k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    _check(q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale)
    mode = resolve_impl(impl)
    if mode == "xla":
        return paged_attention_xla(q, k_pool, v_pool, block_tables,
                                   lengths, k_scale, v_scale)
    return _paged_pallas(q, k_pool, v_pool, block_tables, lengths,
                         k_scale, v_scale, interpret=(mode == "interpret"))
