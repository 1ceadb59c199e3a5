"""Paged decode attention — block-pool KV gather kernels (PR 18, 26, 38).

The continuous batcher's monolithic per-slot KV lanes become a fixed pool
of ``(n_blocks, block_len, heads * head_dim)`` buffers; each decode row
owns a BLOCK TABLE mapping its logical cache blocks to physical pool
blocks (the vLLM paged-attention layout).  This module owns that pool's
DEVICE FORMAT: first the read side, then (``init_pools`` on) what writes it.

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
- ``_paged_kernel`` — Pallas TPU kernel over a grid of rows, one step a
  row (PR 38; (rows, table groups) before).  The block table and the
  lengths ride in as SCALAR-PREFETCH operands
  (``pltpu.PrefetchScalarGridSpec``); the pools stay in HBM (``pl.ANY``)
  and the kernel copies pool blocks HBM->VMEM itself by PHYSICAL id — no
  host gather, no (A, used_len) materialization.
  - A row's step loops over its LIVE groups only (a ``fori_loop`` of
    ``ceil(live blocks / G)`` trips); a group is G consecutive table
    entries, ``G * block_len`` = 128 cache positions at the served shapes,
    folded into the row's online-softmax carry (flash_attention style, PR
    26).  G follows from the shapes the kernel is handed
    (``_group_blocks``): the largest power of two with ``G * block_len <=
    128``, no wider than the table rounded up to a power of two (a narrower
    table is one group, a table that is no multiple of G is padded with
    entries that are never fetched), halved until both pools'
    double-buffered fetch slots fit ``_FETCH_VMEM_BYTES``.
  - A row whose first table entry is block 0, the trash block (how the
    batcher marks an inactive slot), has NO live group whatever its
    length: no DMA, no fold, a zero output row.  What the kernel skips
    follows its inputs alone: each row's length and first table entry.
  - Fetching: one DMA a live block (one holding positions below the row's
    length) into one of two VMEM slots; each fold starts the next live
    group's DMAs (the row's next group, or group 0 of the next row that
    has one) before it waits for its own, so fetch and fold overlap across
    groups and rows; only the call's first group, of its first live row,
    is fetched in the open, and a call without a live row fetches nothing.
    Entries past a row's length are not fetched at all — their buffer rows
    keep stale values and are masked — and a group wholly past it is
    never visited.
  - Per-head reductions over the folded lane axis are matmuls against a
    0/1 head-membership matrix (``_head_segments``), so every in-kernel
    value is a plain 2-D tile; with ~128 rows on the moving side the
    matrix's MXU weight tiles are loaded once per 128 positions.  The
    sums keep float32 accuracy in three bf16 passes (``_dot01``).
  - int8 pools dequantize IN-KERNEL: the per-(block, head) scale rows of
    the group's blocks (fetched beside them) multiply the per-head logits
    / probabilities, never the (positions, width) tile.  Float pools carry
    no scale operands.

``impl`` resolves through ``ops/dispatch.resolve_impl``: Pallas on a TPU
backend, the reference on CPU; ``"interpret"`` runs the kernel on CPU for
the parity tests.

The window format at the end of the file has a decode kernel of its own,
``grouped_paged_attention``: grouped-query pages ((kv_heads, block_len,
head_dim) a block, in the cache's type), several query heads a key head, so
it shares no code with the kernel above; the same grid of one step a row,
the same live-only loop, fetch and idle-slot rules.

Quantization contract: ``inference/quantize.kv_pack_int8`` /
``kv_unpack_int8`` (symmetric, scale = per-(block, head) absmax / 127) —
the ONE contract the kernel above all, and the append and the commit at
the end of this file, share.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.inference.quantize import kv_pack_int8, kv_unpack_int8
from analytics_zoo_tpu.ops.dispatch import resolve_impl

NEG_INF = -1e30
_LANE = 128
_SUBLANE = 8
_GROUP_ROWS = 128               # cache positions one grid step folds
_FETCH_VMEM_BYTES = 4 << 20     # both pools' double-buffered fetch slots


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
    holds the same values; a trash row (first table entry 0) reads zero,
    as in the kernel."""
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
    out = jnp.einsum("bhk,bkhd->bhd", att, vc)
    return jnp.where((block_tables[:, 0] == 0)[:, None, None], 0.0, out)


def _head_segments(nh: int, hd: int):
    """0/1 head-membership matrix (heads * head_dim, heads padded to a
    lane multiple): column h is 1 on head h's lanes.  ``x @ seg`` sums
    each head's lanes (per-head reduce), ``y @ seg.T`` broadcasts a
    per-head value back over them — the folded-lane substitute for a
    (.., heads, head_dim) reshape, which Mosaic cannot do in registers.
    bfloat16 holds 0 and 1 exactly (see ``_dot01``)."""
    seg = np.zeros((nh * hd, -(-nh // _LANE) * _LANE), np.float32)
    seg[np.arange(nh * hd), np.arange(nh * hd) // hd] = 1.0
    return jnp.asarray(seg, jnp.bfloat16)


def _dot01(x, seg, contract):
    """``x`` (f32) contracted with the 0/1 matrix ``seg`` (bf16) to float32
    accuracy in three single-pass MXU matmuls: x splits exactly into three
    bf16 parts (3 x 8 mantissa bits), each product with 0 or 1 is exact and
    the MXU accumulates in float32.  ``Precision.HIGHEST`` would split the
    0/1 side too and pay six passes for the same sums."""
    out = None
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        d = jax.lax.dot_general(part, seg, (contract, ((), ())),
                                preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def _group_blocks(block_len: int, n_table: int, width: int,
                  itemsize: int) -> int:
    """Table entries one grid step folds: the largest power of two with
    ``G * block_len <= 128`` cache positions (the rows one MXU weight tile
    is worth loading for), no wider than the table rounded up to a power of
    two (a narrower table is one group), and with the 2 pools x 2 slots of
    ``(G * block_len, width)`` fetch buffers inside ``_FETCH_VMEM_BYTES``."""
    g = 1
    while (2 * g * block_len <= _GROUP_ROWS and g < n_table
           and 8 * g * block_len * width * itemsize <= _FETCH_VMEM_BYTES):
        g *= 2
    return g


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                  block_len: int, n_table: int, group: int, quant: bool,
                  scale: float):
    """One row's grid step: fold the row's LIVE groups, ``group`` pool
    blocks — ``GB = group * block_len`` cache positions — each, in a loop
    into the row's online-softmax carry (m/l per head, acc per lane), then
    emit.  W = heads * head_dim lanes, P = heads padded to a lane multiple.

    A row whose first table entry is block 0, the trash block (an inactive
    slot), has no live group: no DMA, no fold, a zero output row.

    The pools stay in HBM.  Each fold first starts the DMAs of the NEXT
    live group's blocks — the row's next group, or group 0 of the next row
    that has one — into the other of two VMEM slots, then waits for its
    own, so a group's fetch overlaps the previous group's fold; only the
    call's first group is fetched in the open.  One DMA a live block by
    physical id from the scalar-prefetched table; a block wholly past the
    length is never fetched (its buffer rows keep whatever they held, and
    are masked), a group wholly past it never visited."""
    if quant:
        ks_hbm, vs_hbm, seg_ref, o_ref, m_ref, l_ref, acc_ref, slot_ref, \
            k_buf, v_buf, ks_buf, vs_buf, sems = rest
    else:
        seg_ref, o_ref, m_ref, l_ref, acc_ref, slot_ref, k_buf, v_buf, \
            sems = rest
    a = pl.program_id(0)
    n_rows = pl.num_programs(0)
    gb = group * block_len

    def live_blocks(row):
        # table entries holding positions below the row's length; at least
        # one for a row that is not trash
        return jnp.where(bt_ref[row, 0] == 0, 0,
                         jnp.clip(pl.cdiv(len_ref[row], block_len), 1,
                                  n_table))

    def next_live(row):
        # the first row at or after ``row`` with a live group (n_rows: none)
        return jax.lax.while_loop(
            lambda r: (r < n_rows) & (live_blocks(jnp.minimum(
                r, n_rows - 1)) == 0), lambda r: r + 1, row)

    def fetch(row, grp, slot, wait: bool = False):
        """Start, or wait for, the DMAs of group ``grp`` of ``row`` into
        ``slot`` (a wait rebuilds the descriptors its start used)."""
        n_live = live_blocks(row)
        pairs = [(k_hbm, k_buf), (v_hbm, v_buf)]
        if quant:
            pairs += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]
        for i in range(group):
            j = grp * group + i

            @pl.when(j < n_live)
            def _block():
                blk = bt_ref[row, j]
                for n, (src, dst) in enumerate(pairs):
                    copy = pltpu.make_async_copy(
                        src.at[blk], dst.at[slot, i], sems.at[slot, n])
                    copy.wait() if wait else copy.start()

    def spread(rows):
        # (r, P) per-head values -> (r, W) over each head's lanes: rows @
        # seg.T, contracted on seg's head axis
        return _dot01(rows, seg_ref[...], ((1,), (1,)))

    @pl.when(a == 0)
    def _first():
        slot_ref[0] = 0
        if not quant:
            # masked rows multiply the buffer by 0: never-written float
            # VMEM may hold NaN patterns (any int8 is finite)
            k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        first = next_live(0)

        @pl.when(first < n_rows)
        def _open():
            fetch(first, 0, 0)      # the one fetch nothing overlaps

    n_groups = pl.cdiv(live_blocks(a), group)

    @pl.when(n_groups > 0)
    def _row():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        after = next_live(a + 1)

        def fold(t, carry):
            slot = slot_ref[0]
            more = t + 1 < n_groups

            @pl.when(more | (after < n_rows))
            def _prefetch():
                fetch(jnp.where(more, a, after), jnp.where(more, t + 1, 0),
                      1 - slot)

            fetch(a, t, slot, wait=True)
            slot_ref[0] = 1 - slot

            q = q_ref[0].astype(jnp.float32) * scale                  # (1, W)
            k = k_buf[slot].astype(jnp.float32).reshape(gb, -1)       # (GB, W)
            v = v_buf[slot].astype(jnp.float32).reshape(gb, -1)
            # s[j, h] = ks[block of j, h] * sum_{lanes of h} q * k[j]
            s = _dot01(q * k, seg_ref[...], ((1,), (0,)))             # (GB, P)
            # (a table padded into its last group has positions past its end)
            valid = t * gb + jax.lax.broadcasted_iota(
                jnp.int32, (gb, 1), 0) < jnp.minimum(len_ref[a],
                                                     n_table * block_len)
            if quant:
                def rows(buf):        # (G, 1, P) block scales -> (GB, P)
                    sc = buf[slot]
                    return jnp.broadcast_to(
                        sc, (group, block_len, sc.shape[-1])).reshape(gb, -1)
                s = s * rows(ks_buf)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[...]                                       # (8, P)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new[:1])                                # (GB, P)
            alpha = jnp.exp(m_prev - m_new)                           # (8, P)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0,
                                                      keepdims=True)
            if quant:
                # an unfetched block's scale rows are stale: 0 * stale != 0
                p = jnp.where(valid, p * rows(vs_buf), 0.0)
            # one spread for both per-head factors: the (dequantized)
            # probabilities and the carry rescale (twice: 16-row bf16 tiles)
            wide = spread(jnp.concatenate([p, alpha, alpha], axis=0))
            acc_ref[...] = acc_ref[...] * wide[gb:gb + 1] \
                + jnp.sum(wide[:gb] * v, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, n_groups, fold, 0)
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / spread(
            jnp.concatenate([l, l], axis=0))[:1]).astype(o_ref.dtype)

    @pl.when(n_groups == 0)
    def _trash():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_pallas(q, k_pool, v_pool, block_tables, lengths, k_scale,
                  v_scale, interpret: bool):
    # jitted so that a model's layers, which call this with one set of
    # shapes, share one trace and one Mosaic lowering of the kernel
    A, nh, hd = q.shape
    _, bl, W = k_pool.shape
    n_table = int(block_tables.shape[1])
    quant = k_scale is not None
    group = _group_blocks(bl, n_table, W, k_pool.dtype.itemsize)
    seg = _head_segments(nh, hd)
    P = seg.shape[1]

    def lanes(s):
        # (n_blocks, heads) -> (n_blocks, 1, P): a block's scales are one
        # (1, P) row, and padded heads scale by 0 (they own no lanes)
        return jnp.pad(jnp.asarray(s, jnp.float32),
                       [(0, 0), (0, P - nh)])[:, None, :]

    def row_block(a, bt, ln):
        return (a, 0, 0)

    def whole(a, bt, ln):
        return (0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    fetch_buf = pltpu.VMEM((2, group, bl, W), k_pool.dtype)
    scale_buf = pltpu.VMEM((2, group, 1, P), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(A,),
        in_specs=[pl.BlockSpec((1, 1, W), row_block), in_hbm, in_hbm]
        + [in_hbm, in_hbm] * quant
        + [pl.BlockSpec((W, P), whole)],
        out_specs=pl.BlockSpec((1, 1, W), row_block),
        # m/l carry 8 identical sublanes so every matmul operand built
        # from them is made of whole (8, 128) tiles
        scratch_shapes=[pltpu.VMEM((_SUBLANE, P), jnp.float32),
                        pltpu.VMEM((_SUBLANE, P), jnp.float32),
                        pltpu.VMEM((1, W), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32),
                        fetch_buf, fetch_buf]
        + [scale_buf, scale_buf] * quant
        + [pltpu.SemaphoreType.DMA((2, 4 if quant else 2))])
    kernel = functools.partial(_paged_kernel, block_len=bl, n_table=n_table,
                               group=group, quant=quant,
                               scale=1.0 / np.sqrt(hd))
    scales = (lanes(k_scale), lanes(v_scale)) if quant else ()
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, 1, W), jnp.float32),
        # the fetch slot and the DMAs in flight carry from one row's step
        # to the next: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_int8" if k_pool.dtype == jnp.int8
        else "paged_attention",
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32), q.reshape(A, 1, W), k_pool, v_pool,
      *scales, seg)
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
      batcher's trash block): the reference masks their positions by
      ``lengths``, the kernel does not fetch them.  A row whose FIRST
      entry is block 0 is an inactive slot: whatever its length, its
      output is exactly zero and the kernel reads nothing for it (block 0
      is never allocated to a request).
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


# -- the pool's device format: the write side ---------------------------------
#
# One owner (PR 30): a model builds its paged contract from these
# (``models/textmodels.TransformerLM``), and the scheduler
# (``serving/generate``) hands the pool through as an opaque pytree, never
# naming a leaf.  ``init_pools`` (the zeroed pool), ``pool_commit`` (a
# prefill batch's K/V into blocks), ``pool_gather`` (prefix blocks back out
# as float32 K/V), ``pool_cursor`` + ``pool_append_attend`` (one decode token
# a row: append, then attend), ``pool_bytes`` (each leaf's accounting class).
# A second format, one latent row a token, a third, grouped-query K/V with
# compressed keys and a per-slot recurrent state, and a fourth, grouped-query
# pages beside per-slot rings of a window's K/V, follow at the end of the file.
#
# A pool is a dict of per-layer lists.  ``k`` / ``v``: (n_blocks, block_len,
# heads * head_dim) blocks, float32 or int8; block 0 is the TRASH block
# (padding rows and inactive slots write there).  int8 pools add ``ks`` /
# ``vs``, the (n_blocks, heads) float32 scale planes, and ``stk`` / ``stv``,
# per-slot (max_active, block_len, heads, head_dim) float32 STAGING copies
# of each row's active (partial) block, kept unfolded (``kv_pack_int8``
# reduces per head) so that every append re-quantizes the block from exact
# values.
#
_LEAF_CLASS = {"k": "paged_pool", "v": "paged_pool",
               "ks": "scales", "vs": "scales",
               "stk": "lanes", "stv": "lanes",
               "kv": "paged_pool", "ik": "paged_pool",   # the latent format
               "ck": "paged_pool", "lin": "lanes",       # the grouped format
               "rk": "lanes", "rv": "lanes"}             # the window format


def init_pools(n_layers: int, n_blocks: int, block_len: int, n_head: int,
               head_dim: int, max_active: int, kv_quant: str = "off"):
    """Zeroed host-side pool pytree.  ``n_blocks`` counts the trash block
    (row 0): an allocator hands out ids 1..n-1."""
    if kv_quant not in ("off", "int8"):
        raise ValueError(f"kv_quant must be off|int8, got {kv_quant!r}")
    quant = kv_quant == "int8"
    shapes = {"k": ((n_blocks, block_len, n_head * head_dim),
                    np.int8 if quant else np.float32)}
    shapes["v"] = shapes["k"]
    if quant:
        shapes["ks"] = shapes["vs"] = ((n_blocks, n_head), np.float32)
        shapes["stk"] = shapes["stv"] = (
            (max_active, block_len, n_head, head_dim), np.float32)
    return {name: [np.zeros(shape, dt) for _ in range(n_layers)]
            for name, (shape, dt) in shapes.items()}


def pool_bytes(pools) -> Dict[str, int]:
    """Bytes of a pool (or of its ``ShapeDtypeStruct`` tree) by accounting
    class: ``paged_pool`` (the KV blocks), ``scales`` (int8 scale planes),
    ``lanes`` (per-slot staging buffers)."""
    out = {"paged_pool": 0, "scales": 0, "lanes": 0}
    for name, leaves in pools.items():
        if not isinstance(leaves, (list, tuple)):
            leaves = [leaves]           # one array for every layer
        out[_LEAF_CLASS[name]] += sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in leaves)
    return out


def pool_commit(pools, ks, vs, lengths, dest, slots, *, block_len: int,
                kv_quant: str = "off"):
    """Scatter a prefill batch's (length-masked) K/V into pool blocks:
    ``ks`` / ``vs`` are per-layer (rows, P, heads, head_dim) float32; row
    j's block t lands at pool id ``dest[j, t]`` (0 = trash, for padding
    rows and blocks past the row's fill).  int8 mode quantizes per block
    and parks each row's partial TAIL block in its slot's staging buffer
    (``slots``; the sentinel ``max_active`` drops padding rows), so decode
    appends re-quantize from exact values.  Returns the new pool."""
    bl = int(block_len)
    npb = dest.shape[1]
    bb, pb, nh, hd = ks[0].shape
    pad = npb * bl
    valid = (jnp.arange(pb)[None, :] < lengths[:, None])[..., None, None]
    out = {name: list(leaves) for name, leaves in pools.items()}
    tb = jnp.minimum(lengths // bl, npb - 1)
    tsel = tb[:, None, None, None, None]
    fold = (bb, npb, bl, nh * hd)
    for li in range(len(ks)):
        k = jnp.where(valid, ks[li], 0.0)
        v = jnp.where(valid, vs[li], 0.0)
        if pad > pb:
            z = jnp.zeros((bb, pad - pb, nh, hd), jnp.float32)
            k = jnp.concatenate([k, z], axis=1)
            v = jnp.concatenate([v, z], axis=1)
        kb = k.reshape(bb, npb, bl, nh, hd)
        vb = v.reshape(bb, npb, bl, nh, hd)
        if kv_quant == "int8":
            qk, sk = kv_pack_int8(kb)
            qv, sv = kv_pack_int8(vb)
            out["k"][li] = out["k"][li].at[dest].set(qk.reshape(fold))
            out["v"][li] = out["v"][li].at[dest].set(qv.reshape(fold))
            out["ks"][li] = out["ks"][li].at[dest].set(sk)
            out["vs"][li] = out["vs"][li].at[dest].set(sv)
            tk = jnp.take_along_axis(kb, tsel, axis=1)[:, 0]
            tv = jnp.take_along_axis(vb, tsel, axis=1)[:, 0]
            out["stk"][li] = out["stk"][li].at[slots].set(tk, mode="drop")
            out["stv"][li] = out["stv"][li].at[slots].set(tv, mode="drop")
        else:
            out["k"][li] = out["k"][li].at[dest].set(kb.reshape(fold))
            out["v"][li] = out["v"][li].at[dest].set(vb.reshape(fold))
    return out


def pool_gather(pools, tables, n_head: int):
    """The blocks ``tables`` (rows, n) names, back out of the pool as
    float32 K/V in logical order: two per-layer lists of (rows, n *
    block_len, heads, head_dim), dequantized when the pool is int8 —
    what a suffix-only prefill attends its shared prefix through."""
    def layer(name, scales, li):
        blocks = _gather_dequant(pools[name][li],
                                 pools[scales][li] if scales in pools
                                 else None, tables, n_head)
        A, T, bl, nh, hd = blocks.shape
        return blocks.reshape(A, T * bl, nh, hd)

    n_layers = len(pools["k"])
    return ([layer("k", "ks", li) for li in range(n_layers)],
            [layer("v", "vs", li) for li in range(n_layers)])


def pool_cursor(block_tables, pos, block_len: int):
    """Where each row's next token lands: ``(rows, cur, off)`` = row ids,
    the physical block under the cursor, the offset inside it.  A cursor
    past the table's end keeps rewriting its last entry instead of indexing
    out of range (inactive rows point their whole table at the trash
    block)."""
    rows = jnp.arange(pos.shape[0])
    off = pos % block_len
    cur = block_tables[rows, jnp.minimum(pos // block_len,
                                         block_tables.shape[1] - 1)]
    return rows, cur, off


def pool_append_attend(pools, li: int, q, k, v, cursor, block_tables, pos,
                       *, kv_quant: str = "off",
                       impl: Optional[str] = None):
    """Layer ``li`` of one decode step: append the token's ``k`` / ``v``
    (rows, heads, head_dim) at ``cursor`` (``pool_cursor``), then attend
    ``q`` over the row's table.  int8 mode re-packs the row's ACTIVE block
    from its exact float32 staging copy (reset on block rollover), so a
    value is quantized once, from exact inputs.  Returns ``(o, leaves)``:
    the attention output and the layer's new leaf of every pool part."""
    rows, cur, off = cursor
    A, nh, hd = k.shape
    if kv_quant == "int8":
        keep = (off != 0)[:, None, None, None]
        stk = jnp.where(keep, pools["stk"][li], 0.0).at[rows, off].set(k)
        stv = jnp.where(keep, pools["stv"][li], 0.0).at[rows, off].set(v)
        qk, sk = kv_pack_int8(stk)                    # (A, bl, nh, hd)
        qv, sv = kv_pack_int8(stv)
        fold = (A, stk.shape[1], nh * hd)
        new = {"k": pools["k"][li].at[cur].set(qk.reshape(fold)),
               "v": pools["v"][li].at[cur].set(qv.reshape(fold)),
               "ks": pools["ks"][li].at[cur].set(sk),
               "vs": pools["vs"][li].at[cur].set(sv),
               "stk": stk, "stv": stv}
    else:
        new = {"k": pools["k"][li].at[cur, off].set(k.reshape(A, nh * hd)),
               "v": pools["v"][li].at[cur, off].set(v.reshape(A, nh * hd))}
    o = paged_attention(q, new["k"], new["v"], block_tables, pos + 1,
                        new.get("ks"), new.get("vs"), impl=impl)
    return o, new


# -- the LATENT pool format (PR 32) -------------------------------------------
#
# A latent-attention model (MLA) with a learned selection (an indexer) keeps
# ONE compressed row a token a layer, not per-head K and V, in two pools:
#
# - ``kv``: (n_blocks, block_len, latent_width(latent, rope)) rows of
#   ``[c_kv (latent) | k_r (rope) | zeros]``, padded up to a multiple of the
#   128 lanes (512 + 64 -> 640: 64 lanes = 128 B a token a layer of padding
#   in bfloat16, 9.1 % of the two pools' 1,408 useful bytes), so that the
#   rows a decode step selects come out of the pool in ONE gather;
# - ``ik``: (n_blocks, block_len, index_dim) rows of the indexer's key
#   (128 wide: a lane tile as it is), a pool of its own because a decode step
#   reads EVERY live token's index key and only the selected tokens' latent
#   rows.
#
# Block 0 is the trash block, as in the K/V format; the cache's dtype is the
# model's (bfloat16 as served).  ``init_latent_pools`` (zeroed),
# ``latent_commit`` (one prefilled sequence into its blocks),
# ``latent_gather`` (whole blocks back out, in table order: the indexer's
# keys of a decode row, a shared prefix's rows), ``latent_append`` (one decode
# token a row), ``latent_select`` (the selected tokens' rows through the block
# table, their addresses computed from it); ``pool_cursor`` and ``pool_bytes``
# serve both formats.

def latent_width(latent_dim: int, rope_dim: int) -> int:
    """Lanes of a ``kv`` row: ``latent_dim + rope_dim`` rounded up to 128."""
    return -(-(latent_dim + rope_dim) // _LANE) * _LANE


def init_latent_pools(n_layers: int, n_blocks: int, block_len: int,
                      latent_dim: int, rope_dim: int, index_dim: int, dtype):
    """Zeroed host-side latent pool pytree (``n_blocks`` counts the trash
    block)."""
    width = latent_width(latent_dim, rope_dim)
    return {"kv": [np.zeros((n_blocks, block_len, width), dtype)
                   for _ in range(n_layers)],
            "ik": [np.zeros((n_blocks, block_len, index_dim), dtype)
                   for _ in range(n_layers)]}


def latent_rows(c_kv, k_r, width: int, dtype):
    """``[c_kv | k_r | zeros]`` rows of the ``kv`` pool, (..., width)."""
    pad = width - c_kv.shape[-1] - k_r.shape[-1]
    return jnp.concatenate(
        [c_kv, k_r, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)],
        axis=-1).astype(dtype)


def latent_commit(pools, kvs, iks, dest, *, block_len: int):
    """ONE prefilled sequence into the pool: ``kvs`` / ``iks`` are per-layer
    (P, width) / (P, index_dim) rows, block t of which lands at pool id
    ``dest[t]`` (0 = trash, for blocks past the sequence's fill).  Returns
    the new pool."""
    bl, npb = int(block_len), dest.shape[0]

    def blocks(rows):
        pad = npb * bl - rows.shape[0]
        if pad:
            rows = jnp.concatenate(
                [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)])
        return rows.reshape(npb, bl, rows.shape[1])

    return dict(pools,
                kv=[p.at[dest].set(blocks(r).astype(p.dtype))
                    for p, r in zip(pools["kv"], kvs)],
                ik=[p.at[dest].set(blocks(r).astype(p.dtype))
                    for p, r in zip(pools["ik"], iks)])


def latent_gather(pool, tables):
    """The blocks ``tables`` (rows, n) names out of one layer's ``kv`` or
    ``ik`` pool, in table order: (rows, n * block_len, width)."""
    blocks = jnp.take(pool, tables, axis=0)
    return blocks.reshape(blocks.shape[0], -1, blocks.shape[-1])


def latent_append(pools, li: int, kv_rows, ik_rows, cursor):
    """Layer ``li`` of one decode step: each row's token row into the block
    under its ``cursor`` (``pool_cursor``).  Returns the layer's two new
    leaves ``(kv, ik)``."""
    _, cur, off = cursor
    kv, ik = pools["kv"][li], pools["ik"][li]
    return (kv.at[cur, off].set(kv_rows.astype(kv.dtype)),
            ik.at[cur, off].set(ik_rows.astype(ik.dtype)))


def latent_select(pool, block_tables, sel, block_len: int):
    """The ``kv`` rows of the cache positions ``sel`` (rows, k) of each row,
    through its block table: (rows, k, width).  The sparse selection inside
    paged attention: only these rows are read, in the stage's ONE gather.
    A row's address is computed, not looked up: position ``s`` lies under
    table entry ``s // block_len``, and that entry's block id is the sum of
    the row's table under the mask ``s // block_len == arange(n_table)``
    (int32, so exact; a reduce over (rows, n_table, k) that is fused and
    never stored)."""
    entry = jnp.arange(block_tables.shape[1], dtype=sel.dtype)
    hit = (sel // block_len)[:, None, :] == entry[None, :, None]
    blk = jnp.where(hit, block_tables[:, :, None], 0).sum(axis=1)
    flat = blk * block_len + sel % block_len
    return jnp.take(pool.reshape(-1, pool.shape[-1]), flat, axis=0)


# -- the GROUPED-QUERY pool format (PR 35) -------------------------------------
#
# A decoder whose attention layers share each key/value head among a GROUP of
# query heads and keep, for a long context, only the best BLOCKS of it (chosen
# per key head from compressed keys), beside layers that hold no keys at all
# but a fixed-size recurrent state.  Four leaves:
#
# - ``k`` / ``v``: a layer a (n_blocks, kv_heads, block_len, head_dim) pool.
#   The key head comes BEFORE the positions: the key heads keep different
#   blocks, so what a decode step gathers is one key head's (block_len,
#   head_dim) tile of one block, contiguous and whole lane tiles wide (64 x
#   128 bfloat16 = 16 KB), never a strided half of a wider row;
# - ``ck``: a layer a (n_blocks, windows * kv_heads * head_dim) pool of the
#   compressed keys: window j of a sequence (tokens ``j * stride ...``) lies
#   in the block its first token lies in, at slot ``j % windows`` of that
#   block's row, ``[slot 0 | slot 1 | ...]``, each slot (kv_heads, head_dim)
#   flat.  One row a block, so that scoring a context gathers whole rows of 2
#   KB through the table and a 4-row tile is never padded to a sublane tile;
# - ``lin``: ONE (linear layers, max_active, heads, dim, dim) float32 array,
#   the recurrent state of every slot (no block table: a slot's state is its
#   own); stacked by layer, so that a run of like layers scans over it.
#
# Block 0 is the trash block.  ``init_grouped_pools`` (zeroed),
# ``grouped_commit`` (one prefilled sequence into its blocks),
# ``grouped_append`` (one decode token a row), ``grouped_rows`` (single
# positions back out: the window a decode step completes), ``compressed_put``
# (that window's key into its slot), ``compressed_gather`` (a table's
# compressed keys, in order), ``grouped_state_put`` (a prefilled sequence's
# recurrent states into its slot), ``grouped_blocks`` (whole kept blocks of each key
# head); ``pool_cursor`` and ``pool_bytes`` serve this format too.

def init_grouped_pools(n_layers: int, n_blocks: int, block_len: int,
                       kv_heads: int, head_dim: int, windows: int,
                       n_linear: int, max_active: int, lin_heads: int,
                       lin_dim: int, dtype):
    """Zeroed host-side state pytree (``n_blocks`` counts the trash block):
    ``n_layers`` attention layers' pools and ``n_linear`` layers' states."""
    kv = (n_blocks, kv_heads, block_len, head_dim)
    return {"k": [np.zeros(kv, dtype) for _ in range(n_layers)],
            "v": [np.zeros(kv, dtype) for _ in range(n_layers)],
            "ck": [np.zeros((n_blocks, windows * kv_heads * head_dim), dtype)
                   for _ in range(n_layers)],
            "lin": np.zeros((n_linear, max_active, lin_heads, lin_dim,
                             lin_dim), np.float32)}


def grouped_commit(pools, ks, vs, cks, dest, *, block_len: int):
    """ONE prefilled sequence into the pools: ``ks`` / ``vs`` per-layer (P,
    kv_heads, head_dim) rows, ``cks`` per-layer (P // stride, kv_heads,
    head_dim) compressed keys (window j at row j); block t lands at pool id
    ``dest[t]`` (0 = trash).  Returns the new ``k``, ``v``, ``ck`` lists (a
    pool without ``ck``, the window format's, gives an empty third)."""
    bl, npb = int(block_len), dest.shape[0]

    def blocks(rows, per_block):
        pad = npb * per_block - rows.shape[0]
        if pad:
            rows = jnp.concatenate(
                [rows, jnp.zeros((pad,) + rows.shape[1:], rows.dtype)])
        return rows.reshape((npb, per_block) + rows.shape[1:])

    def kv(p, rows):
        return p.at[dest].set(
            blocks(rows, bl).transpose(0, 2, 1, 3).astype(p.dtype))

    def ck(p, rows):
        per = p.shape[1] // (rows.shape[1] * rows.shape[2])
        return p.at[dest].set(
            blocks(rows, per).reshape(npb, -1).astype(p.dtype))

    return ([kv(p, r) for p, r in zip(pools["k"], ks)],
            [kv(p, r) for p, r in zip(pools["v"], vs)],
            [ck(p, r) for p, r in zip(pools.get("ck", ()), cks)])


def _grouped_row_ids(blk, off, G: int, bl: int):
    """Flat row ids ``((blk * G + g) * bl + off)`` of one position's rows in
    a pool seen as (n_blocks * G * bl, head_dim): (..., G)."""
    return (blk[..., None] * G + jnp.arange(G, dtype=blk.dtype)) * bl \
        + off[..., None]


def grouped_append(pools, li: int, k_rows, v_rows, cursor):
    """Layer ``li`` of one decode step: each row's ``k`` / ``v`` (rows,
    kv_heads, head_dim) into the block under its ``cursor``
    (``pool_cursor``).  Returns the layer's new ``(k, v)`` leaves.  Every
    access of this format sees the pool as rows or whole tiles of ONE
    row-major buffer, so that the compiler keeps one layout for it."""
    _, cur, off = cursor
    k, v = pools["k"][li], pools["v"][li]
    _, G, bl, d = k.shape
    ids = _grouped_row_ids(cur, off, G, bl).reshape(-1)

    def put(pool, rows):
        return pool.reshape(-1, d).at[ids].set(
            rows.reshape(-1, d).astype(pool.dtype)).reshape(pool.shape)

    return put(k, k_rows), put(v, v_rows)


def grouped_rows(pool, block_tables, positions, block_len: int):
    """The rows of the cache positions ``positions`` (rows, n) of each row,
    through its block table: (rows, n, kv_heads, head_dim)."""
    _, G, bl, d = pool.shape
    entry = jnp.clip(positions // block_len, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, entry, axis=1)
    return jnp.take(pool.reshape(-1, d), _grouped_row_ids(
        blk, positions % block_len, G, bl), axis=0)


def compressed_put(ck_pool, block_tables, window, rows, on, windows: int):
    """Window ``window`` (rows,) of each row gets the compressed key
    ``rows`` (rows, kv_heads, head_dim) where ``on`` (rows,); the others
    write to the trash block.  A block's row is read, one slot of it
    replaced, and written back whole."""
    A = rows.shape[0]
    flat = rows.reshape(A, -1)
    entry = jnp.clip(window // windows, 0, block_tables.shape[1] - 1)
    blk = jnp.where(on, jnp.take_along_axis(
        block_tables, entry[:, None], axis=1)[:, 0], 0)
    slot = jnp.arange(ck_pool.shape[1]) // flat.shape[1]
    new = jnp.where(slot[None, :] == (window % windows)[:, None],
                    jnp.tile(flat, (1, windows)).astype(ck_pool.dtype),
                    ck_pool[blk])
    return ck_pool.at[blk].set(new)


def grouped_state_put(lin, states, slot):
    """One prefilled sequence's recurrent states ``states`` (layers, heads,
    dim, dim) into slot ``slot`` of ``lin``: a slice a layer, in place (a
    scatter along the slot axis makes the compiler re-lay the whole array
    out, there and back)."""
    for li in range(lin.shape[0]):
        lin = jax.lax.dynamic_update_slice(
            lin, states[li][None, None].astype(lin.dtype),
            (li, slot, 0, 0, 0))
    return lin


def compressed_gather(ck_pool, block_tables, kv_heads: int, head_dim: int):
    """Every compressed key under ``block_tables`` (rows, n), in window
    order: (rows, n * windows, kv_heads, head_dim)."""
    got = jnp.take(ck_pool, block_tables, axis=0)
    return got.reshape(got.shape[0], -1, kv_heads, head_dim)


def grouped_blocks(pool, blocks):
    """Whole blocks of each key head: ``blocks`` (rows, kv_heads, n) pool
    ids, head g's from head g's part of the pool: (rows, kv_heads, n,
    block_len, head_dim), one gather of contiguous tiles."""
    n_blocks, G, bl, d = pool.shape
    flat = blocks * G + jnp.arange(G, dtype=blocks.dtype)[None, :, None]
    return jnp.take(pool.reshape(n_blocks * G, bl, d), flat, axis=0)


# -- the WINDOW pool format ---------------------------------------------------
#
# A grouped-query decoder whose layers attend either the whole context (FULL
# layers) or its last ``window`` positions (WINDOW layers).  A full layer keeps
# the grouped-query pages above (``k`` / ``v``: a layer a (n_blocks, kv_heads,
# block_len, head_dim) pool, written by ``grouped_commit`` / ``grouped_append``
# and read through the block table by ``grouped_blocks``); a window layer keeps
# for each slot a RING of its last ``window`` positions:
#
# - ``rk`` / ``rv``: a window layer a (max_active, kv_heads, window, head_dim)
#   array; position p of a slot's sequence lies at ring row ``p % window``.
#   A slot's ring is its own (no block table: the allocator's blocks hold the
#   full layers alone), so a window layer's state does not grow with the
#   context, and a slot's rows are one contiguous (window, head_dim) tile a
#   key head.
#
# ``init_window_pools`` (zeroed), ``ring_commit`` (a prefilled sequence's last
# ``window`` rows of one layer into its slot's ring), ``ring_put`` (one decode
# token a row), ``grouped_paged_attention`` (a full layer's decode read: a
# Pallas kernel over each row's own live pages); ``pool_cursor`` and
# ``pool_bytes`` serve this format too.

def init_window_pools(n_full: int, n_window: int, n_blocks: int,
                      block_len: int, kv_heads: int, head_dim: int,
                      window: int, max_active: int, dtype):
    """Zeroed host-side state pytree (``n_blocks`` counts the trash block):
    ``n_full`` full layers' pages and ``n_window`` window layers' rings."""
    kv = (n_blocks, kv_heads, block_len, head_dim)
    ring = (max_active, kv_heads, window, head_dim)
    return {"k": [np.zeros(kv, dtype) for _ in range(n_full)],
            "v": [np.zeros(kv, dtype) for _ in range(n_full)],
            "rk": [np.zeros(ring, dtype) for _ in range(n_window)],
            "rv": [np.zeros(ring, dtype) for _ in range(n_window)]}


def ring_commit(ring, rows, length, slot):
    """ONE prefilled sequence into slot ``slot`` of one window layer's ring
    (slots, kv_heads, window, head_dim): of ``rows`` (S, kv_heads, head_dim),
    the last ``min(length, window)`` real positions, position p at ring row
    ``p % window``; a ring row no position reaches (a sequence shorter than
    the window) is zeroed.  A slice of the ring, written in place."""
    W = ring.shape[2]
    r = jnp.arange(W)
    # the latest real position p with p % W == r (negative: none)
    p = r + W * ((length - 1 - r) // W)
    got = jnp.take(rows, jnp.clip(p, 0, rows.shape[0] - 1), axis=0)
    got = jnp.where((p >= 0)[:, None, None], got, 0).transpose(1, 0, 2)
    return jax.lax.dynamic_update_slice(
        ring, got[None].astype(ring.dtype), (slot, 0, 0, 0))


def ring_put(ring, rows, pos, on):
    """One decode token a row: row a's ``rows[a]`` (kv_heads, head_dim) into
    ring row ``pos[a] % window`` of slot a (decode row a IS slot a), where
    ``on[a]``; an idle slot's ring is left as it is.  The ring is seen as
    rows of ONE row-major buffer, as ``grouped_append`` sees its pool."""
    A, G, W, d = ring.shape
    ids = (jnp.arange(A)[:, None] * G + jnp.arange(G)) * W \
        + (pos % W)[:, None]
    ids = jnp.where(on[:, None], ids, A * G * W)         # past the end: dropped
    return ring.reshape(-1, d).at[ids.reshape(-1)].set(
        rows.reshape(-1, d).astype(ring.dtype), mode="drop").reshape(
        ring.shape)


_FOLD_BYTES = 1 << 20       # K and V bytes one fold of the grouped kernel moves


def _fold_blocks(n_table: int, block_bytes: int) -> int:
    """Table entries one fold of ``_grouped_kernel`` takes: the smallest power
    of two whose K and V blocks (``block_bytes`` each) move ``_FOLD_BYTES``, no
    wider than the table rounded up to a power of two."""
    f = 1
    while 2 * f * block_bytes < _FOLD_BYTES and f < n_table:
        f *= 2
    return f


def _grouped_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, m_ref, l_ref,
                    acc_ref, slot_ref, k_buf, v_buf, sems, *, block_len: int,
                    n_table: int, fold: int, scale: float):
    """One row's grid step over grouped-query pages: fold the row's LIVE table
    entries, ``fold`` blocks (``FB = fold * block_len`` positions) at a time,
    into each key head's online-softmax carry (m / l per query row, acc per
    query row and lane), then emit.  Query head ``g * R + j`` (R query rows a
    key head, padded to a sublane multiple) reads key head g.

    A row whose first table entry is block 0, the trash block (an idle slot),
    has no live block: no DMA, no fold, a zero output row.

    The pools stay in HBM.  One DMA a live block for K and one for V (a
    block's key heads are one contiguous (kv_heads, block_len, head_dim)
    tile), by physical id from the scalar-prefetched table, into one of two
    VMEM slots; each fold first starts the NEXT live fold's DMAs (the row's
    next fold, or fold 0 of the next row that has a live block), then waits
    for its own, so a fold's fetch overlaps the previous fold's arithmetic.
    A block wholly past the length is never fetched (its buffer rows keep
    whatever they held, and are masked)."""
    a = pl.program_id(0)
    n_rows = pl.num_programs(0)
    G = k_buf.shape[2]
    fb = fold * block_len

    def live_blocks(row):
        return jnp.where(bt_ref[row, 0] == 0, 0,
                         jnp.clip(pl.cdiv(len_ref[row], block_len), 1,
                                  n_table))

    def next_live(row):
        # the first row at or after ``row`` with a live block (n_rows: none)
        return jax.lax.while_loop(
            lambda r: (r < n_rows) & (live_blocks(jnp.minimum(
                r, n_rows - 1)) == 0), lambda r: r + 1, row)

    def fetch(row, t, slot, wait: bool = False):
        """Start, or wait for, the DMAs of fold ``t`` of ``row`` into
        ``slot`` (a wait rebuilds the descriptors its start used)."""
        n_live = live_blocks(row)
        for i in range(fold):
            j = t * fold + i

            @pl.when(j < n_live)
            def _block():
                blk = bt_ref[row, j]
                for n, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    copy = pltpu.make_async_copy(
                        src.at[blk], dst.at[slot, i], sems.at[slot, n])
                    copy.wait() if wait else copy.start()

    @pl.when(a == 0)
    def _first():
        slot_ref[0] = 0
        # masked positions multiply the buffer by 0: never-written VMEM may
        # hold NaN patterns
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        first = next_live(0)

        @pl.when(first < n_rows)
        def _open():
            fetch(first, 0, 0)      # the one fetch nothing overlaps

    n_folds = pl.cdiv(live_blocks(a), fold)

    @pl.when(n_folds > 0)
    def _row():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        after = next_live(a + 1)
        length = jnp.minimum(len_ref[a], n_table * block_len)

        def body(t, carry):
            slot = slot_ref[0]
            more = t + 1 < n_folds

            @pl.when(more | (after < n_rows))
            def _prefetch():
                fetch(jnp.where(more, a, after), jnp.where(more, t + 1, 0),
                      1 - slot)

            fetch(a, t, slot, wait=True)
            slot_ref[0] = 1 - slot
            valid = t * fb + jax.lax.broadcasted_iota(
                jnp.int32, (1, fb), 1) < length
            for g in range(G):
                k = k_buf[slot, :, g].reshape(fb, -1)             # (FB, d)
                v = v_buf[slot, :, g].reshape(fb, -1)
                q = q_ref[0, g].astype(k.dtype)                   # (R, d)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale   # (R, FB)
                s = jnp.where(valid, s, NEG_INF)
                # m / l keep a lane tile of copies: read one back by a max
                m_prev = jnp.max(m_ref[g], axis=1, keepdims=True)   # (R, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                    keepdims=True))
                p = jnp.exp(s - m_new)                            # (R, FB)
                alpha = jnp.exp(m_prev - m_new)                   # (R, 1)
                m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n_folds, body, 0)
        for g in range(G):
            l = jnp.max(l_ref[g], axis=1, keepdims=True)
            o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)

    @pl.when(n_folds == 0)
    def _idle():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_pallas(q, k_pool, v_pool, block_tables, lengths,
                    interpret: bool):
    # jitted so that a model's full layers, which call this with one set of
    # shapes, share one trace and one Mosaic lowering of the kernel
    A, G, J, d = q.shape
    bl = k_pool.shape[2]
    n_table = int(block_tables.shape[1])
    R = -(-J // _SUBLANE) * _SUBLANE
    fold = _fold_blocks(n_table, G * bl * d * k_pool.dtype.itemsize)

    def row_block(a, bt, ln):
        return (a, 0, 0, 0)

    rows = pl.BlockSpec((1, G, R, d), row_block)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    fetch_buf = pltpu.VMEM((2, fold, G, bl, d), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(A,),
        in_specs=[rows, in_hbm, in_hbm],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((G, R, _LANE), jnp.float32),
                        pltpu.VMEM((G, R, _LANE), jnp.float32),
                        pltpu.VMEM((G, R, d), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32),
                        fetch_buf, fetch_buf,
                        pltpu.SemaphoreType.DMA((2, 2))])
    kernel = functools.partial(_grouped_kernel, block_len=bl,
                               n_table=n_table, fold=fold,
                               scale=1.0 / np.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, G, R, d), jnp.float32),
        # the fetch slot and the DMAs in flight carry from one row's step
        # to the next: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="grouped_paged_attention",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.pad(q.astype(jnp.float32), [(0, 0), (0, 0), (0, R - J), (0, 0)]),
      k_pool, v_pool)
    return out[:, :, :J]


def grouped_paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                            interpret: bool = False):
    """One decode token a row over grouped-query pages, through a Pallas
    kernel that reads each row's own live blocks and no others.

    - ``q`` (rows, kv_heads, group, head_dim): the current token's queries,
      query head ``g * group + j`` of key head g.
    - ``k_pool`` / ``v_pool`` (n_blocks, kv_heads, block_len, head_dim), the
      cache's type (the operands of both products: scores and the
      probabilities are cast to it, both accumulate in float32).
    - ``block_tables`` (rows, n_table) int32; a row whose FIRST entry is block
      0 is an idle slot: its output is exactly zero and nothing is read for
      it.  Entries past a row's length are never read.
    - ``lengths`` (rows,) int32 >= 1: the valid positions (``pos + 1`` at
      decode time: the current token's K/V is appended before the read).

    Returns (rows, kv_heads, group, head_dim) float32.  The XLA path that a
    CPU process serves through, and the kernel's oracle, is the chunked
    gather of ``models/window_moe_lm.WindowMoELM._full_decode``; ``interpret``
    runs the kernel on any backend (the parity tests)."""
    q = jnp.asarray(q)
    if q.ndim != 4 or k_pool.ndim != 4 or v_pool.shape != k_pool.shape \
            or k_pool.shape[1] != q.shape[1] \
            or k_pool.shape[3] != q.shape[3]:
        raise ValueError(
            f"q (rows, kv_heads, group, head_dim) {q.shape} does not match "
            f"pools (n_blocks, kv_heads, block_len, head_dim) {k_pool.shape}"
            f" / {v_pool.shape}")
    return _grouped_pallas(q, k_pool, v_pool, block_tables, lengths,
                           interpret=interpret)
