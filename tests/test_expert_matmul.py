"""``ops/expert_matmul.grouped_expert_ffn`` (interpreted) against the
``jax.lax.ragged_dot`` slabs of ``models/lm_common.routed_experts``
(``impl="xla"``, the kernel's oracle), through ``routed_experts`` itself:
the layer's output ``y`` and its four counts, at toy widths on the CPU.

Both sides multiply the same rounded operands and accumulate in float32;
they differ in summation order alone (and, in bfloat16, where that order
moves the gate's product across a rounding step before the last matmul).
Outputs here are of magnitude ~1-20: ``ATOL`` is 5e-5 in float32 (measured
up to ~1.4e-5) and 2e-3 in bfloat16.
"""
import numpy as np
import pytest

ATOL = {"float32": 5e-5, "bfloat16": 2e-3}

# name: (tokens, top_k, experts routed over, held (first, count), scoring,
#        the experts a selection bias forces every token onto, every
#        third token not valid)
CASES = {
    # WindowMoELM's structure: softmax over the top-k, ReLU, all held
    "softmax_relu_all_held": (16, 3, 8, (0, 8), "softmax", None, False),
    # LatentMoELM's: sigmoid + selection bias, SiLU, a strict share held,
    # pairs that land on experts held elsewhere
    "sigmoid_silu_held_share": (16, 4, 32, (4, 8), "sigmoid", None, False),
    # two experts take every token: groups of 40 outgrow a window of rows
    "a_group_larger_than_the_row_window": (40, 2, 8, (0, 8), "sigmoid",
                                           (1, 3), False),
    "every_pair_on_one_expert": (40, 1, 8, (0, 8), "sigmoid", (5,), False),
    # experts 1-6 have no pair between the touched 0 and 7
    "experts_with_no_pairs_between_touched_ones": (24, 2, 8, (0, 8),
                                                   "sigmoid", (0, 7), False),
    "rows_not_valid": (16, 3, 8, (0, 8), "softmax", None, True),
    # 130 x 4 = 520 pairs: above the kernel's bound, ragged_dot on both
    "above_the_pair_bound": (130, 4, 8, (0, 8), "softmax", None, False),
}


def _layer(case, dtype):
    import jax.numpy as jnp
    T, k, E, (first, count), scoring, forced, invalid = CASES[case]
    g = np.random.default_rng(len(case))
    H, F = 128, 256
    bias = 0.1 * g.normal(size=(E,))
    if forced:
        bias[list(forced)] += 10.0       # beyond any sigmoid score
    blk = {"router": g.normal(size=(H, E)).astype(np.float32) / 8,
           "e_bias": bias.astype(np.float32)}
    for name, shape in (("w_gate", (count, H, F)), ("w_up", (count, H, F)),
                        ("w_down", (count, F, H))):
        blk[name] = jnp.asarray(g.normal(size=shape) / 8, dtype)
    h = jnp.asarray(g.normal(size=(T, H)), jnp.float32)
    valid = jnp.asarray(np.arange(T) % 3 != 0 if invalid
                        else np.ones(T, bool))
    return h, blk, valid, dict(top_k=k, held=(first, count),
                               dtype=jnp.dtype(dtype), scoring=scoring,
                               scale=2.5 if scoring == "sigmoid" else 1.0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_equals_the_ragged_dot_path(case, dtype):
    """``routed_experts`` with ``impl="interpret"`` against ``impl="xla"``:
    ``y`` agrees to summation order, the counts are equal, and the kernel is
    in the layer exactly when the call has at most ``MAX_PAIRS`` pairs."""
    import jax
    from analytics_zoo_tpu.models import lm_common as common
    from analytics_zoo_tpu.ops import expert_matmul
    h, blk, valid, kw = _layer(case, dtype)
    act = jax.nn.silu if kw["scoring"] == "sigmoid" else jax.nn.relu
    T, k = h.shape[0], kw["top_k"]
    out, jaxprs = {}, {}
    for impl in ("interpret", "xla"):
        def layer(h, blk, valid, impl=impl):
            return common.routed_experts(h, h, blk, valid, act=act,
                                         impl=impl, **kw)
        jaxprs[impl] = str(jax.make_jaxpr(layer)(h, blk, valid))
        y, c = jax.jit(layer)(h, blk, valid)
        out[impl] = np.asarray(y), {n: int(v) for n, v in c.items()}
    (y_k, c_k), (y_x, c_x) = out["interpret"], out["xla"]
    assert c_k == c_x
    kernel = T * k <= expert_matmul.MAX_PAIRS
    assert ("name=grouped_expert_ffn" in jaxprs["interpret"]) == kernel
    assert "name=grouped_expert_ffn" not in jaxprs["xla"]
    assert ("ragged_dot" in jaxprs["interpret"]) != kernel
    T_valid = int(np.asarray(valid).sum())
    assert c_x["pairs"] == T_valid * k
    forced = CASES[case][5]
    if forced:
        assert c_x["touched"] == len(forced)
        assert c_x["busiest"] == T_valid * k // len(forced)
    if kernel:
        np.testing.assert_allclose(y_k, y_x, rtol=0, atol=ATOL[dtype])
    else:
        np.testing.assert_array_equal(y_k, y_x)
    # rows not valid route nothing
    assert not y_x[~np.asarray(valid)].any()
    assert np.abs(y_x).max() > 0.5


@pytest.mark.parametrize("tile_bytes", [None, 3 * 128 * 128 * 4],
                         ids=["one_tile", "two_tiles"])
def test_the_kernel_streams_an_expert_in_tiles_of_its_width(monkeypatch,
                                                            tile_bytes):
    """The kernel alone against the dense form over pairs sorted by expert,
    with expert groups of 0-19 rows (one outgrowing a window of 16 float32
    rows; some empty, the first and last among them) and trailing rows held
    by none (zero out): once with the whole width ``F`` a grid step and
    once cut to two 128-lane tiles (``_STEP_BYTES`` lowered), which must
    sum to the same products."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import expert_matmul
    if tile_bytes:
        monkeypatch.setattr(expert_matmul, "_STEP_BYTES", tile_bytes)
    expert_matmul._expert_pallas.clear_cache()
    g = np.random.default_rng(3)
    E, H, F, P = 6, 128, 256, 40
    sizes = np.asarray([0, 9, 3, 0, 19, 0], np.int32)
    wg, wu = (g.normal(size=(E, H, F)).astype(np.float32) / 8
              for _ in range(2))
    wd = g.normal(size=(E, F, H)).astype(np.float32) / 8
    x = g.normal(size=(P, H)).astype(np.float32)
    assert expert_matmul._f_tile(H, F, 4) == (128 if tile_bytes else F)
    got = np.asarray(expert_matmul.grouped_expert_ffn(
        jnp.asarray(x), wg, wu, wd, jnp.asarray(sizes), act=jax.nn.silu,
        interpret=True))
    expert_matmul._expert_pallas.clear_cache()
    owner = np.repeat(np.arange(E), sizes)
    want = np.zeros((P, H), np.float32)
    for p, e in enumerate(owner):
        gate = x[p] @ wg[e]
        want[p] = (gate / (1 + np.exp(-gate)) * (x[p] @ wu[e])) @ wd[e]
    assert not got[sizes.sum():].any()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
