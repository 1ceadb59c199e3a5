"""Pipeline parallelism — GPipe-style microbatch pipelining over the `pipe` mesh axis.

Green-field (the reference has no pipeline parallelism, SURVEY.md §2.3).  Design for
homogeneous stages (e.g. transformer blocks): per-stage parameters are STACKED on a
leading axis sharded P('pipe'), so each device holds exactly its stage's weights.
Inside `shard_map`, the schedule runs M + S - 1 ticks: stage 0 injects microbatch t at
tick t, every stage applies its block and hands the activation to the next stage over
ICI via `lax.ppermute`, and the last stage's outputs are all-gathered at the end.
Forward AND backward differentiate through scan+ppermute, so the same program trains.

Bubble fraction is (S-1)/(M+S-1) — pick microbatches >> stages as usual.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.common.context import PIPE_AXIS


def stack_stage_params(params_list):
    """Stack per-stage param pytrees along a new leading axis (to shard P('pipe'))."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *params_list)


def _pipeline_local(stage_params, x, *, stage_fn, axis_name: str):
    """Per-device body.  stage_params: leaves (1, ...) — this device's stage slice;
    x: (M, Bm, ...) full microbatched input (replicated)."""
    params = jax.tree.map(lambda a: a[0], stage_params)
    S = jax.lax.axis_size(axis_name)
    s = jax.lax.axis_index(axis_name)
    M = x.shape[0]
    perm = [(i, (i + 1) % S) for i in range(S)]
    # activation buffer entering this stage each tick; pcast marks it varying over
    # the pipe axis (shard_map manual-axes typing)
    zero_act = jax.lax.pcast(jnp.zeros_like(x[0]), (axis_name,),
                             to="varying")

    def tick(carry, t):
        act = carry
        mb = jnp.clip(t, 0, M - 1)
        inp = jnp.where(s == 0, x[mb], act)
        out = stage_fn(params, inp)
        nxt = jax.lax.ppermute(out, axis_name, perm)
        return nxt, out

    _, outs = jax.lax.scan(tick, zero_act, jnp.arange(M + S - 1))
    # last stage's outputs for microbatch m appear at tick m + S - 1
    results = outs[S - 1:]
    mask = (s == S - 1).astype(results.dtype)
    return jax.lax.psum(results * mask, axis_name)   # broadcast from last stage


def pipeline_apply(stage_fn: Callable, stacked_params, x_microbatches,
                   mesh: Mesh, axis_name: str = PIPE_AXIS):
    """Run x through S pipelined stages.

    stage_fn(params, x) -> y with y.shape == x.shape (homogeneous stages).
    stacked_params: leaves (S, ...); x_microbatches: (M, Bm, ...).
    Returns (M, Bm, ...) outputs (replicated over the pipe axis)."""
    pspec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    fn = jax.shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params), P()),
        out_specs=P())
    return fn(stacked_params, x_microbatches)


def pipeline_apply_stages(stage_fns, stage_params_list, x_microbatches,
                          mesh: Mesh, axis_name: str = PIPE_AXIS):
    """HETEROGENEOUS GPipe (round 5): stage i applies its OWN function and
    its OWN param pytree — structures may differ freely across stages (the
    stacked-params `pipeline_apply` requires homogeneous stages).

    Mechanics: each stage's pytree ravels to a flat vector, the vectors pad
    to a common length and stack on a leading axis sharded P(pipe) — every
    device holds ONLY its stage's weights (plus the pad), and inside
    `shard_map` each device unflattens its slice and applies its stage via
    `lax.switch`.  Params travel one stacked f32 buffer but unflatten back
    to their ORIGINAL leaf dtypes before the stage runs, and gradients
    return in the caller's dtypes (the astype transpose casts back —
    verified with bf16 params).  Constraint shared with all GPipe schedules
    here: activations crossing stage boundaries (and the injected
    microbatch input) must share one shape/dtype, since they travel one
    `ppermute` buffer.

    stage_fns: [fn_i(params_i, x) -> y] with y.shape == x.shape;
    stage_params_list: their pytrees; x_microbatches: (M, Bm, ...).
    Returns (M, Bm, ...) outputs (replicated over the pipe axis)."""
    from jax.flatten_util import ravel_pytree

    S = len(stage_fns)
    if mesh.shape[axis_name] != S:
        raise ValueError(f"mesh {axis_name} axis is {mesh.shape[axis_name]} "
                         f"but {S} stages were given")
    # Each stage's pytree ravels to a flat f32 vector; vectors pad to a
    # common length and STACK on a leading axis sharded P(pipe) — the same
    # proven sharded-params path as the homogeneous pipeline (each device
    # holds only its stage's weights, and the shard_map transpose psums the
    # per-device grads correctly; explicit replicated params or closures do
    # NOT transpose through the stage switch).
    flats = [ravel_pytree(p) for p in stage_params_list]
    sizes = [int(v.size) for v, _ in flats]
    L = max(sizes)
    stacked = jnp.stack([jnp.pad(v.astype(jnp.float32), (0, L - n))
                         for (v, _), n in zip(flats, sizes)])
    unflattens = [u for _, u in flats]

    def local(pv, x):
        # pv: (1, L) — this device's stage vector
        vec = pv[0]
        s = jax.lax.axis_index(axis_name)
        M = x.shape[0]
        perm = [(i, (i + 1) % S) for i in range(S)]
        zero_act = jax.lax.pcast(jnp.zeros_like(x[0]), (axis_name,),
                                 to="varying")
        branches = [
            functools.partial(
                lambda f, u, n, t: f(u(vec[:n]), t), f, u, n)
            for f, u, n in zip(stage_fns, unflattens, sizes)]

        def tick(carry, t):
            act = carry
            mb = jnp.clip(t, 0, M - 1)
            inp = jnp.where(s == 0, x[mb], act)
            out = jax.lax.switch(s, branches, inp)
            nxt = jax.lax.ppermute(out, axis_name, perm)
            return nxt, out

        _, outs = jax.lax.scan(tick, zero_act, jnp.arange(M + S - 1))
        results = outs[S - 1:]
        mask = (s == S - 1).astype(results.dtype)
        return jax.lax.psum(results * mask, axis_name)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(axis_name), P()),
                       out_specs=P())
    return fn(stacked, x_microbatches)


def to_microbatches(x, n_micro: int):
    B = x.shape[0]
    assert B % n_micro == 0, f"batch {B} not divisible by {n_micro} microbatches"
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


def from_microbatches(y):
    return y.reshape((-1,) + y.shape[2:])
