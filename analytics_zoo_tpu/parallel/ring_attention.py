"""Ring attention — sequence/context parallelism over the mesh `seq` axis.

Green-field (SURVEY.md §5 long-context: the reference has NO sequence parallelism; its
TransformerLayer materialises the full (T, T) matrix).  Design: shard the sequence axis
of q/k/v across devices; each step every device computes attention of its local query
block against the k/v block it currently holds, accumulates via online softmax
(flash-attention statistics m/l), then rotates k/v one hop around the ring with
`lax.ppermute` — compute overlaps the ICI transfer and full attention is recovered in
`seq` hops with O(T/n) memory per device.

Causal masking uses absolute positions, so fully-masked future blocks contribute zero
(their statistics are washed out by the online-softmax correction term).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.common.context import SEQ_AXIS
from analytics_zoo_tpu.ops.dispatch import on_tpu


def _ring_local(q, k, v, *, axis_name: str, causal: bool,
                scale: Optional[float]):
    """Per-shard body.  q/k/v: (B, H, T_local, D)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    s = scale if scale is not None else 1.0 / np.sqrt(D)
    q32 = q.astype(jnp.float32)
    q_pos = idx * Tq + jnp.arange(Tq)

    # derive accumulators from q so they carry the same varying-axis type as the
    # rotating k/v blocks (shard_map manual-axes typing)
    o0 = q32 * 0.0
    l0 = q32[..., 0] * 0.0
    m0 = q32[..., 0] * 0.0 - 1e30
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        o, l, m, k_blk, v_blk = carry
        src = (idx - i) % n
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32,
                            k_blk.astype(jnp.float32)) * s
        if causal:
            k_pos = src * Tk + jnp.arange(Tk)
            mask = k_pos[None, :] <= q_pos[:, None]          # (Tq, Tk)
            logits = jnp.where(mask[None, None], logits, -1e9)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, l, m_new, k_blk, v_blk

    o, l, _, _, _ = jax.lax.fori_loop(0, n, body, (o0, l0, m0, k, v))
    return (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def _ring_local_flash(q, k, v, *, axis_name: str, causal: bool,
                      scale: Optional[float]):
    """Per-shard body with the Pallas flash kernel computing each hop
    (round 5): O(block) VMEM per hop instead of the O(T_local^2) logits the
    einsum body materializes — ring handles the cross-chip axis, flash the
    on-chip blocks, so sequence length is bounded by neither.  Hop partials
    merge exactly through their log-sum-exp statistics
    (flash_attention_with_lse; o = sum_i o_i * exp(lse_i - lse_total)),
    and the merge is differentiable end to end (the lse cotangent enters
    the flash backward as a delta shift)."""
    from analytics_zoo_tpu.ops.flash_attention import flash_attention_with_lse

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    NEG = jnp.float32(-1e30)

    def full_hop(args):
        qq, kb, vb = args
        # f32 hop partials: the accumulator stays full-precision across all
        # hops (like the einsum body), rounding once at the end
        return flash_attention_with_lse(qq, kb, vb, False, s,
                                        out_dtype=jnp.float32)

    def diag_hop(args):
        qq, kb, vb = args
        return flash_attention_with_lse(qq, kb, vb, causal, s,
                                        out_dtype=jnp.float32)

    def masked_hop(args):
        qq, _, _ = args
        return (jnp.zeros(qq.shape, jnp.float32),
                jnp.full(qq.shape[:-1], NEG, jnp.float32))

    o0 = (q.astype(jnp.float32) * 0.0)
    l0 = q.astype(jnp.float32)[..., 0] * 0.0 + NEG
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        o_run, lse_run, k_blk, v_blk = carry
        src = (idx - i) % n
        if causal:
            o_h, lse_h = jax.lax.cond(
                src == idx, diag_hop,
                lambda args: jax.lax.cond(src < idx, full_hop, masked_hop,
                                          args),
                (q, k_blk, v_blk))
        else:
            o_h, lse_h = full_hop((q, k_blk, v_blk))
        lse_new = jnp.logaddexp(lse_run, lse_h)
        w_old = jnp.exp(lse_run - lse_new)[..., None]
        w_new = jnp.exp(lse_h - lse_new)[..., None]
        o_run = o_run * w_old + o_h * w_new
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o_run, lse_new, k_blk, v_blk

    o, _, _, _ = jax.lax.fori_loop(0, n, body, (o0, l0, k, v))
    return o.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                   scale: Optional[float] = None,
                   axis_name: str = SEQ_AXIS, impl: str = "auto"):
    """q/k/v: (B, H, T, D) with T sharded over `axis_name`.  Returns attention output
    with the same sharding.  Equivalent to full softmax attention (see tests).

    impl: "xla" (einsum hop body — materializes (T_local, T_local) logits
    per hop), "flash" (Pallas flash kernel per hop, O(block) memory — the
    long-context composition), or "auto" (flash from the measured T>=1024
    crossover on TPU, else xla)."""
    n = mesh.shape[axis_name]
    t_local = q.shape[2] // max(n, 1)
    if impl == "auto":
        from analytics_zoo_tpu.ops.attention import _flash_worthwhile
        # same eligibility gates as the single-chip flash dispatch
        # (_select_flash): measured crossover AND the kernel's head-dim limit
        impl = ("flash" if on_tpu()
                and _flash_worthwhile(t_local) and q.shape[-1] <= 256
                else "xla")
    if impl not in ("flash", "xla"):
        raise ValueError(f"unknown ring attention impl {impl!r} "
                         "(expected 'auto', 'flash', or 'xla')")
    body = (_ring_local_flash if impl == "flash" else _ring_local)
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(body, axis_name=axis_name, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call outputs carry no varying-mesh-axes typing, so the
        # flash body opts out of vma checking (all its inputs/outputs are
        # uniformly seq-sharded; the einsum body keeps full checking)
        check_vma=(impl != "flash"))
    return fn(q, k, v)


def sequence_sharded_spec(mesh: Mesh, axis_name: str = SEQ_AXIS):
    return P(None, None, axis_name, None)
