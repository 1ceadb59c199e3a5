"""The routed experts of a decode step as ONE Pallas kernel: every held
expert with at least one pair streams its three weight matrices once, an
``F``-tile at a time, and the next tile's fetch (the same expert's next tile,
or the next touched expert's first) is in flight while the current tile
multiplies, so the weights stream back to back across expert boundaries.

``grouped_expert_ffn(x, w_gate, w_up, w_down, sizes, act=...)`` computes,
for pair rows ``x`` sorted by expert, ``act(x W_gate) * (x W_up)`` then
``W_down`` of each row's own expert.  ``models/lm_common.routed_experts``
calls it for a call of at most ``MAX_PAIRS`` pairs (a decode step) and keeps
``jax.lax.ragged_dot`` slabs for larger calls (prefill) and as the CPU path
and the kernel's oracle (``impl="xla"``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_PAIRS = 512         # pairs a call may have to take the kernel (rows and
                        # output stay resident in VMEM)
_STEP_BYTES = 5 << 20   # weight bytes one grid step fetches: three F-tiles
_LANE = 128


def _f_tile(H: int, F: int, itemsize: int) -> int:
    """The ``F``-tile one grid step takes: the widest multiple of 128 lanes
    that divides ``F`` and whose three tiles (``H`` x tile of ``w_gate`` and
    ``w_up``, tile x ``H`` of ``w_down``) move at most ``_STEP_BYTES``, so
    that two buffers of them fit the scoped VMEM; ``F`` itself where it is
    no multiple of 128."""
    if F % _LANE:
        return F
    fits = [t for t in range(_LANE, F + 1, _LANE)
            if F % t == 0 and 3 * H * t * itemsize <= _STEP_BYTES]
    return max(fits, default=_LANE)


def _expert_kernel(ids_ref, start_ref, size_ref, n_ref, x_ref, wg_ref,
                   wu_ref, wd_ref, o_ref, *, act, rows: int, align: int):
    """Grid step (e, f): tile f of the e-th touched expert.  Its pairs are
    rows ``[start, start + size)`` of ``x``; it multiplies windows of
    ``rows`` rows from the ``align``-aligned row at or before ``start``
    (more than one only when the group outgrows a window) and adds each
    window's product to the output rows of its own group alone, so every
    pair's row is written by its own expert, summed over the tiles in
    float32.  Steps past the touched experts compute nothing (their blocks
    repeat the last one's, so nothing is fetched for them)."""
    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _zero():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(e < n_ref[0])
    def _expert():
        start, size = start_ref[e], size_ref[e]
        lo = start // align * align

        def window(w, carry):
            r0 = pl.multiple_of(lo + w * rows, align)
            x = x_ref[pl.ds(r0, rows), :]
            g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
            mid = (act(g) * u).astype(wd_ref.dtype)
            out = jnp.dot(mid, wd_ref[0], preferred_element_type=jnp.float32)
            r = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            mine = (r >= start) & (r < start + size)
            o_ref[pl.ds(r0, rows), :] += jnp.where(mine, out, 0.0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(start + size - lo, rows), window, 0)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _expert_pallas(x, w_gate, w_up, w_down, sizes, act, interpret: bool):
    # jitted so that a model's expert layers, which call this with one set
    # of shapes, share one trace and one Mosaic lowering of the kernel
    P, H = x.shape
    E, _, F = w_gate.shape
    dt = jnp.dtype(w_gate.dtype)
    align = 8 * max(1, 4 // dt.itemsize)     # a sublane tile of the type
    rows = 2 * align
    tf = _f_tile(H, F, dt.itemsize)
    nf = F // tf
    sizes = jnp.asarray(sizes, jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    touched = sizes > 0
    n = touched.sum().astype(jnp.int32)
    # touched experts first, in order; the rest repeat the last touched one
    ids = jnp.argsort(jnp.where(touched, 0, 1), stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(E) < n, ids, ids[jnp.maximum(n - 1, 0)])
    # a window from the last group's aligned start stays inside the rows
    P_pad = -(-P // align) * align + rows
    xp = jnp.pad(x.astype(dt), [(0, P_pad - P), (0, 0)])

    def tile(e, f, n_ref):
        return jnp.where(e < n_ref[0], f, nf - 1)

    def gate_up(e, f, ids_ref, st, sz, n_ref):
        return (ids_ref[e], 0, tile(e, f, n_ref))

    def down(e, f, ids_ref, st, sz, n_ref):
        return (ids_ref[e], tile(e, f, n_ref), 0)

    def whole(e, f, *_):
        return (0, 0)

    step = 3 * H * tf * dt.itemsize
    resident = P_pad * H * (dt.itemsize + 4)        # x and the output
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(E, nf),
        in_specs=[pl.BlockSpec((P_pad, H), whole),
                  pl.BlockSpec((1, H, tf), gate_up),
                  pl.BlockSpec((1, H, tf), gate_up),
                  pl.BlockSpec((1, tf, H), down)],
        out_specs=pl.BlockSpec((P_pad, H), whole))
    out = pl.pallas_call(
        functools.partial(_expert_kernel, act=act, rows=rows, align=align),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P_pad, H), jnp.float32),
        # the output accumulates over the whole grid: the steps run in
        # order; two buffers of every block, and room for the products
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * (step + resident) + (4 << 20)),
        interpret=interpret,
        name="grouped_expert_ffn",
    )(ids, starts[ids], sizes[ids], n[None], xp, w_gate, w_up, w_down)
    return out[:P]


def grouped_expert_ffn(x, w_gate, w_up, w_down, sizes, *, act=jax.nn.silu,
                       interpret: bool = False):
    """Each pair row's own expert over pair rows sorted by expert.

    - ``x`` (pairs, H): the rows, expert 0's first; cast to the weights'
      type (the operands of all three products, which accumulate in
      float32; the gate's product ``act(g) * u`` is cast to it as well).
    - ``w_gate`` / ``w_up`` (experts, H, F), ``w_down`` (experts, F, H).
    - ``sizes`` (experts,) int32: rows each expert owns, in order from row
      0; rows past ``sum(sizes)`` belong to none and come out zero.

    Returns (pairs, H) float32.  The XLA path (``jax.lax.ragged_dot`` in
    ``models/lm_common.routed_experts``) is its oracle; ``interpret`` runs
    the kernel on any backend (the parity tests)."""
    if x.ndim != 2 or w_gate.ndim != 3 or w_up.shape != w_gate.shape \
            or w_down.shape != (w_gate.shape[0], w_gate.shape[2],
                                w_gate.shape[1]) \
            or x.shape[1] != w_gate.shape[1] \
            or sizes.shape != (w_gate.shape[0],):
        raise ValueError(
            f"x (pairs, H) {x.shape}, w_gate / w_up (experts, H, F) "
            f"{w_gate.shape} / {w_up.shape}, w_down (experts, F, H) "
            f"{w_down.shape} and sizes (experts,) {sizes.shape} disagree")
    return _expert_pallas(x, w_gate, w_up, w_down, sizes, act=act,
                          interpret=interpret)
