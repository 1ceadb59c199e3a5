"""Selective state-space scan (the Mamba-1 mixer's recurrence) and its causal
depthwise convolution, in their two forms, plain XLA.

Per channel ``c`` and state index ``n``, with a step ``delta_t`` (channels,)
> 0, ``A`` (n, channels) < 0 and the input-dependent ``B_t`` / ``C_t`` (n,)::

    s_t = exp(delta_t A) * s_(t-1) + B_t (delta_t u_t)      y_t = C_t s_t

The state ``s`` is (n, channels): the channel axis last, so that it lies in
the lanes (a (channels, 16) state would pad 16 lanes to 128).  The skip term
``D u_t`` and the output gate belong to the caller.

- ``scan_chunk``: the recurrence over consecutive positions of one sequence,
  position by position in a ``lax.scan`` whose body holds ``unroll`` steps
  (one fusion of several steps, the state read and written once a trip);
  what a prefill runs a chunk of positions at a time, the state carried
  from chunk to chunk.  A position whose ``delta`` is 0 leaves the state
  exactly as it was (``exp(0) = 1``, ``0 * u = 0``): a caller masks padding
  so, and the state that comes back is the one after the last real position.
- ``scan_step``: one token a row: what a decode step runs.

``conv_chunk`` / ``conv_step`` are the causal depthwise convolution of width
K in front of it, ``out_t = b + sum_k w[k] u_(t - K + 1 + k)``, whose carry is
the K - 1 inputs before the current one.

Everything is elementwise float32 (``C_t s_t`` too, as a sum over ``n``: a
matmul would round its operands on a TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv_chunk(u, w, b, carry, n_live):
    """``u`` (P, channels) inputs at consecutive positions, ``carry`` (K - 1,
    channels) the K - 1 inputs before the first of them, ``w`` (K, channels),
    ``b`` (channels,).  Returns ``(out (P, channels), carry')``, float32,
    ``carry'`` the K - 1 inputs that end with the ``n_live``-th of ``u``."""
    K = w.shape[0]
    P = u.shape[0]
    ext = jnp.concatenate([carry.astype(jnp.float32), u.astype(jnp.float32)])
    w = w.astype(jnp.float32)
    out = b.astype(jnp.float32) + sum(w[k] * ext[k:k + P] for k in range(K))
    return out, jax.lax.dynamic_slice_in_dim(ext, n_live, K - 1)


def conv_step(u, w, b, carry):
    """One input a row: ``u`` (rows, channels), ``carry`` (rows, K - 1,
    channels).  Returns ``(out (rows, channels), carry')``, float32."""
    ext = jnp.concatenate([carry.astype(jnp.float32),
                           u.astype(jnp.float32)[:, None]], axis=1)
    out = b.astype(jnp.float32) + (w.astype(jnp.float32)[None] * ext).sum(1)
    return out, ext[:, 1:]


def scan_chunk(u, delta, A, B, C, state, *, unroll: int = 8):
    """``u`` / ``delta`` (P, channels), ``B`` / ``C`` (P, n), ``A`` (n,
    channels), ``state`` (n, channels).  Returns ``(y (P, channels),
    state')``, float32."""
    f32 = jnp.float32

    def step(s, inp):
        u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[None, :] * A) * s + b_t[:, None] * (d_t * u_t)[None, :]
        return s, (c_t[:, None] * s).sum(0)

    P = u.shape[0]
    state, y = jax.lax.scan(
        step, state.astype(f32),
        (u.astype(f32), delta.astype(f32), B.astype(f32), C.astype(f32)),
        unroll=max(1, min(int(unroll), P)))
    return y, state


def scan_step(u, delta, A, B, C, state):
    """One token a row: ``u`` / ``delta`` (rows, channels), ``B`` / ``C``
    (rows, n), ``state`` (rows, n, channels).  Returns ``(y (rows,
    channels), state')``, float32."""
    f32 = jnp.float32
    u, delta, B, C = (a.astype(f32) for a in (u, delta, B, C))
    state = jnp.exp(delta[:, None, :] * A[None]) * state.astype(f32) \
        + B[:, :, None] * (delta * u)[:, None, :]
    return (C[:, :, None] * state).sum(1), state
