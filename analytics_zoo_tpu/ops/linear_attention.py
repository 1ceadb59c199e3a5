"""Decayed linear attention (PR 35): the recurrence of the lightning
attention family in its two forms, plain XLA.

Per head, with a decay ``lambda`` in (0, 1] and a state ``S`` (d, d_v)::

    S_t = lambda * S_(t-1) + k_t^T v_t          o_t = q_t S_t

- ``step``: the recurrence as written, one token a row: what a decode step
  runs.  Elementwise in float32 (the state is read and written once).
- ``chunked``: the same recurrence over T positions, ``chunk`` at a time:
  inside a chunk the decay-masked ``(Q K^T) V`` (two matmuls), across chunks
  the carried state (two more), so a prefill costs matmuls and not T serial
  steps.  Every decay factor that appears is a power of ``lambda`` with a
  non-negative exponent (the mask ``lambda^(i-j)`` is built whole, never as
  ``lambda^i * lambda^-j``), so nothing overflows at any chunk length.

``lengths`` makes ``chunked`` ragged: positions at or past a row's length are
padding; they neither enter the state nor decay it, so the state that comes
back is the one after the row's LAST REAL position, whatever the padding.
(Their outputs are finite and mean nothing.)

Scaling (``1 / sqrt(d)``), norms, rotary and gates belong to the caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def step(q, k, v, decay, S):
    """One token a row.  ``q`` / ``k`` (B, H, d), ``v`` (B, H, d_v),
    ``decay`` (H,), ``S`` (B, H, d, d_v) float32.  Returns ``(o (B, H, d_v),
    S')`` in float32."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    S = decay.astype(jnp.float32)[None, :, None, None] * S \
        + k[..., :, None] * v[..., None, :]
    return (q[..., :, None] * S).sum(axis=-2), S


def chunked(q, k, v, decay, S0, lengths, *, chunk: int = 256, dtype=None):
    """``q`` / ``k`` (B, T, H, d), ``v`` (B, T, H, d_v) at consecutive
    positions, ``decay`` (H,), ``S0`` (B, H, d, d_v) the state before the
    first of them, ``lengths`` (B,) how many of the T are real.  Matmul
    operands in ``dtype`` (default: ``q``'s), sums in float32.  Returns
    ``(o (B, T, H, d_v) float32, S (B, H, d, d_v) float32)``: the outputs
    and the state after each row's last real position."""
    B, T, H, d = q.shape
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"{T} positions are no multiple of the chunk {C}")
    dt = q.dtype if dtype is None else dtype
    log_l = jnp.log(decay.astype(jnp.float32))                      # (H,)
    i = jnp.arange(C)
    gap = i[:, None] - i[None, :]
    # D[h, i, j] = lambda_h ** (i - j) where j <= i, else 0
    D = jnp.where(gap >= 0, jnp.exp(log_l[:, None, None]
                                    * jnp.maximum(gap, 0)), 0.0)
    carry_in = jnp.exp(log_l[None, :] * (i[:, None] + 1.0))         # (C, H)
    lengths = jnp.asarray(lengths, jnp.int32)

    def ein(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=jnp.float32)

    def one(S, x):
        qc, kc, vc, start = x                        # (B, C, H, d), scalar
        nv = jnp.clip(lengths - start, 0, C)                        # (B,)
        real = i[None, :] < nv[:, None]                             # (B, C)
        kc = jnp.where(real[..., None, None], kc, 0.0)
        att = ein("bihd,bjhd->bhij", qc, kc) * D[None]
        o = ein("bhij,bjhe->bihe", att, vc) \
            + ein("bihd,bhde->bihe", qc * carry_in[None, :, :, None], S)
        # the state after the chunk's last REAL position nv - 1
        left = (nv[:, None] - 1 - i[None, :]).astype(jnp.float32)   # (B, C)
        w = jnp.where(real[..., None],
                      jnp.exp(log_l[None, None, :]
                              * jnp.maximum(left, 0.0)[..., None]), 0.0)
        S = jnp.exp(log_l[None, :] * nv[:, None].astype(jnp.float32))[
            ..., None, None] * S \
            + ein("bjhd,bjhe->bhde", kc * w[..., None], vc)
        return S, o

    def chunks(a):
        return a.reshape((B, T // C, C) + a.shape[2:]).swapaxes(0, 1)

    S, o = jax.lax.scan(one, S0.astype(jnp.float32),
                        (chunks(q), chunks(k), chunks(v),
                         jnp.arange(T // C) * C))
    return o.swapaxes(0, 1).reshape(B, T, H, v.shape[-1]), S
