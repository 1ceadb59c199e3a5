"""Post-training weight quantization for the inference path (PR 14).

The reference's optimized-inference story is OpenVINO int8 with VNNI
(pipeline/inference/OpenVinoInferenceSupportive.scala:1-631,
OpenVINOModel.scala:1-214) — calibrate on sample data, quantize weights and
activations to int8, run on the CPU's int8 dot units.  The TPU-native
equivalent here produces weights that stay COMPACT in HBM and serve through
the fused-dequant kernels in ``ops/quant_matmul.py``:

  * **W8A8** (``bits=8``): symmetric per-OUTPUT-CHANNEL int8 weights
    (w_q = round(w / s_w), s_w = absmax_channel / 127) + symmetric
    per-tensor activation scales from a calibration sweep — compute is
    s8 x s8 -> s32 on the MXU, dequantized by ``s_x * s_w`` on the output
    tile (~4x less weight HBM per predict than f32).
  * **W4A16** (``bits=4``): weight-only symmetric int4 with GROUP-WISE
    scales along the contraction axis (two weights per byte,
    ``group_size`` rows per scale) — activations stay 16/32-bit, ~8x less
    weight HBM, the usual int4 recipe for memory-bound serving.

Calibration (``calibrate`` / ``calibrate_featureset``) records each
quantizable layer's input magnitude keyed by its PATH in the params tree
(two same-named layers in different containers calibrate independently —
the bare-name keying this replaces shared one absmax between them and
quantized whichever sub-dict a depth-first search found first).  Next to
plain absmax, ``percentile=99.9`` clips the activation range at that
percentile of |x| — outlier-robust scales for heavy-tailed activations.

Only Dense and the _ConvND family are quantized; everything else (BN folded
stats, pooling, activations) stays in the float path.  For W8A8, layers the
calibration sweep never saw (absmax missing/zero) are left in float; W4A16
is weight-only, so no calibration is required.

Usage:
    absmax = calibrate(model, params, state, calib_inputs)       # or
    absmax = calibrate_featureset(model, params, state, fs, n_batches=8)
    qparams = quantize_params(model, params, absmax)             # int8
    qparams = quantize_params(model, params, {}, bits=4)         # int4
    y = model.apply(qparams, state, x, training=False)
or via InferenceModel.do_quantize(calib, bits=8|4).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.nn.layers.conv import _ConvND
from analytics_zoo_tpu.nn.layers.core import Dense

QUANTIZABLE = (Dense, _ConvND)

# leaves the quantizer emits; weight-byte accounting + "already quantized"
# detection key off these
QUANT_LEAVES = ("W_q", "W_q4", "s_w", "s_g", "s_x")

# per-layer cap on the |x| sample kept for percentile calibration: enough
# for a stable tail estimate, bounded regardless of batch count/size
_PCTL_SAMPLE = 8192


def _target_layers(model, params) -> List[Tuple[object, dict, str]]:
    """(layer, its params, path) for every quantizable layer, recursing
    into containers (Sequential.layers_list / graph Model.graph_layers).
    ``path`` is the slash-joined key chain inside ``params`` — the
    collision-proof identity two same-named layers in different containers
    do not share."""
    out = []

    def walk(layer, p, path):
        if isinstance(layer, QUANTIZABLE) and isinstance(p, dict) \
                and ("W" in p or "W_q" in p or "W_q4" in p):
            out.append((layer, p, path or layer.name))
            return
        subs = getattr(layer, "graph_layers", None) or \
            getattr(layer, "layers_list", None)
        if subs:
            for sub in subs:
                if isinstance(p, dict) and sub.name in p:
                    walk(sub, p[sub.name],
                         f"{path}/{sub.name}" if path else sub.name)

    walk(model, params, "")
    return out


def calibrate(model, params, state, calib_inputs,
              percentile: Optional[float] = None) -> Dict[str, float]:
    """Run ``calib_inputs`` (one batch or a list of batches) through the
    model EAGERLY, recording each quantizable layer's input magnitude.
    Returns ``{layer_path: clip}`` where clip is the absmax (default) or,
    with ``percentile=p``, the p-th percentile of |x| over the sweep —
    robust scales when a few outliers would otherwise stretch the int8
    range over mostly-empty codes."""
    if percentile is not None and not (0.0 < float(percentile) <= 100.0):
        raise ValueError(f"percentile={percentile!r}: expected (0, 100]")
    records: Dict[str, float] = {}
    samples: Dict[str, List[np.ndarray]] = {}
    saved = []
    for layer, _, path in _target_layers(model, params):
        orig = layer.call

        def wrapped(p, x, *, training=False, rng=None,
                    _path=path, _orig=orig):
            ax = jnp.abs(x)
            a = float(jnp.max(ax))
            records[_path] = max(records.get(_path, 0.0), a)
            if percentile is not None:
                flat = np.asarray(ax, np.float32).ravel()
                stride = max(1, flat.size // _PCTL_SAMPLE)
                kept = samples.setdefault(_path, [])
                kept.append(flat[::stride][:_PCTL_SAMPLE])
                if sum(c.size for c in kept) > 4 * _PCTL_SAMPLE:
                    # fold down so the retained sample stays bounded over
                    # arbitrarily long calibration sweeps, not per batch
                    merged = np.concatenate(kept)
                    st = max(1, merged.size // _PCTL_SAMPLE)
                    kept[:] = [merged[::st][:_PCTL_SAMPLE]]
            return _orig(p, x, training=training, rng=rng)

        layer.call = wrapped
        saved.append((layer, orig))
    try:
        batches_ = calib_inputs if isinstance(calib_inputs, list) \
            else [calib_inputs]
        for xb in batches_:
            model.apply(params, state, xb, training=False)
    finally:
        for layer, orig in saved:
            try:
                del layer.call          # restore the class method
            except AttributeError:
                layer.call = orig
    if percentile is not None:
        for path, chunks in samples.items():
            clip = float(np.percentile(np.concatenate(chunks),
                                       float(percentile)))
            # the clip can only TIGHTEN the absmax range; a degenerate
            # all-tiny sample must not zero the scale out entirely
            if clip > 0.0:
                records[path] = min(records[path], clip)
    return records


def calibrate_featureset(model, params, state, fs, n_batches: int = 8,
                         batch_size: int = 32,
                         percentile: Optional[float] = None
                         ) -> Dict[str, float]:
    """Draw the calibration sample from a ``FeatureSet`` iterator (the
    training-side data abstraction) instead of hand-built arrays: the
    first ``n_batches`` batches of ``fs.batches(batch_size)`` — labels and
    pad-weights dropped, inputs fed through :func:`calibrate`."""
    batches = []
    for item in fs.batches(int(batch_size)):
        x = item[0] if isinstance(item, tuple) else item
        batches.append(list(x) if isinstance(x, (list, tuple)) else x)
        if len(batches) >= int(n_batches):
            break
    if not batches:
        raise ValueError("calibrate_featureset: the FeatureSet yielded no "
                         "batches")
    return calibrate(model, params, state, batches, percentile=percentile)


def _locate_holder(tree: dict, path: str):
    """The dict holding ``path``'s final segment, navigated by the exact
    key chain (never a depth-first name search — that is the collision
    bug this replaces)."""
    segs = path.split("/")
    cur = tree
    for seg in segs[:-1]:
        cur = cur[seg]
    return cur, segs[-1]


def _quantize_w8(W: np.ndarray, a: float) -> dict:
    red = tuple(range(W.ndim - 1))   # all but the output-channel axis
    s_w = np.maximum(np.abs(W).max(axis=red), 1e-12) / 127.0
    W_q = np.clip(np.round(W / s_w), -127, 127).astype(np.int8)
    return {"W_q": jnp.asarray(W_q),
            "s_w": jnp.asarray(s_w, jnp.float32),
            "s_x": jnp.asarray(a / 127.0, jnp.float32)}


def _quantize_w4(W: np.ndarray, group_size: int) -> dict:
    """Symmetric int4 with group-wise scales: the weight tensor flattens
    to (K, N) over all-but-the-output-channel axis, groups run along K.
    The requested group size is NORMALIZED to ``ceil(K / ceil(K/gs))`` so
    the effective size is derivable from the stored shapes alone (jitted
    consumers reconstruct it without a side-channel leaf)."""
    from analytics_zoo_tpu.ops import quant_matmul as qm
    n = W.shape[-1]
    k = int(np.prod(W.shape[:-1]))
    W2 = W.reshape(k, n)
    g = max(1, -(-k // max(1, int(group_size))))
    gs = -(-k // g)                  # effective group size (see docstring)
    s_rows = np.empty((g, n), np.float32)
    q = np.empty((k, n), np.int8)
    for i in range(g):
        lo, hi = i * gs, min((i + 1) * gs, k)
        s = np.maximum(np.abs(W2[lo:hi]).max(axis=0), 1e-12) / 7.0
        s_rows[i] = s
        q[lo:hi] = np.clip(np.round(W2[lo:hi] / s), -7, 7).astype(np.int8)
    return {"W_q4": jnp.asarray(qm.pack_int4(q)),
            "s_g": jnp.asarray(s_rows, jnp.float32)}


# -- int8 KV-cache packing (PR 18 paged KV) -----------------------------------
# The ONE pack/unpack contract of the paged pool's owner
# (ops/paged_attention.py: the kernels that read it, the decode append and
# the prefill commit that write it): symmetric int8 with one scale per
# (block, head) — same recipe as `_quantize_w8` (scale = absmax/127,
# round-clip to [-127, 127]) but jnp-traceable, because the quantize
# happens INSIDE the compiled decode/commit programs as tokens append.

def kv_pack_int8(x):
    """Quantize KV block(s) ``x`` (..., block_len, heads, head_dim) f32 ->
    ``(q int8 same shape, scale f32 (..., heads))``.  The scale is the
    per-(block, head) absmax over the (block_len, head_dim) axes — padded
    /unwritten positions must arrive ZEROED so they cannot inflate it
    (zeros quantize to zero exactly at any scale)."""
    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(-3, -1))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale[..., None, :, None]), -127, 127)
    return q.astype(jnp.int8), scale


def kv_unpack_int8(q, scale):
    """Inverse of :func:`kv_pack_int8`: int8 blocks + per-(block, head)
    scales -> f32 values (exact for zeros; |err| <= scale/2 elsewhere)."""
    return jnp.asarray(q, jnp.float32) * jnp.asarray(
        scale, jnp.float32)[..., None, :, None]


def quantize_params(model, params, absmax: Dict[str, float], bits: int = 8,
                    group_size: int = 64):
    """Return a new params pytree with quantizable layers' weights replaced
    by their quantized leaves:

    - ``bits=8``: {"W_q" int8, "s_w" f32 per-out-channel, "s_x" f32
      scalar, "b"?} — layers ``absmax`` never saw stay float.
    - ``bits=4``: {"W_q4" uint8 nibble-packed, "s_g" f32 (groups, out),
      "b"?} — weight-only, every quantizable layer converts (``absmax``
      is not consulted).

    ``absmax`` keys are layer PATHS (see :func:`calibrate`); bare layer
    names are accepted for top-level layers, where path == name."""
    if bits not in (8, 4):
        raise ValueError(f"bits={bits!r}: expected 8 or 4")

    def copy_tree(p):
        return {k: copy_tree(v) if isinstance(v, dict) else v
                for k, v in p.items()}

    qp = copy_tree(params)
    for layer, _, path in _target_layers(model, params):
        a = absmax.get(path, absmax.get(layer.name, 0.0))
        if bits == 8 and a <= 0.0:
            continue                     # never calibrated: leave in float
        holder, key = _locate_holder(qp, path)
        lp = holder[key]
        if "W" not in lp:
            if bits == 8 and "W_q" in lp and a > 0.0:
                # already int8: re-calibration refreshes the activation
                # scale
                lp["s_x"] = jnp.asarray(a / 127.0, jnp.float32)
            continue                     # already quantized otherwise
        W = np.asarray(lp["W"], np.float32)
        new = _quantize_w8(W, a) if bits == 8 \
            else _quantize_w4(W, group_size)
        if "b" in lp:
            new["b"] = lp["b"]
        holder[key] = new
    return qp


def quantize(model, params, state, calib_inputs, bits: int = 8,
             group_size: int = 64, percentile: Optional[float] = None):
    """calibrate + quantize_params in one call.  ``calib_inputs`` may be a
    ``FeatureSet`` (sampled via :func:`calibrate_featureset`), a batch / a
    list of batches, or None for the weight-only ``bits=4`` mode."""
    from analytics_zoo_tpu.feature.dataset import FeatureSet
    if calib_inputs is None:
        if bits == 8:
            raise ValueError("int8 quantization needs calibration inputs "
                             "(activation scales); bits=4 is weight-only")
        absmax: Dict[str, float] = {}
    elif isinstance(calib_inputs, FeatureSet):
        absmax = calibrate_featureset(model, params, state, calib_inputs,
                                      percentile=percentile)
    else:
        absmax = calibrate(model, params, state, calib_inputs,
                           percentile=percentile)
    return quantize_params(model, params, absmax, bits=bits,
                           group_size=group_size)


# -- introspection / accounting ------------------------------------------------

def quantized_bits(params) -> int:
    """0 (float), 8 or 4 — what the params tree serves with.  Mixed trees
    report the SMALLEST width present (the headline compression)."""
    bits = 0
    for path, _ in _leaf_items(params):
        leaf = path.rsplit("/", 1)[-1]
        if leaf == "W_q4":
            return 4
        if leaf == "W_q":
            bits = 8
    return bits


def _leaf_items(params):
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        segs = []
        for p in path:
            segs.append(str(getattr(p, "key", getattr(p, "idx", p))))
        yield "/".join(segs), leaf


def weight_bytes(params) -> int:
    """Bytes of parameters read from HBM per forward pass — every leaf of
    the tree (weights, scales, biases) at its stored dtype.  The
    STRUCTURAL half of the quantized-serving claim: int8 trees come out
    ~4x smaller than f32, int4 ~8x, independent of wall clocks."""
    total = 0
    for _, leaf in _leaf_items(params):
        total += int(np.size(leaf)) * int(np.dtype(
            getattr(leaf, "dtype", np.float32)).itemsize)
    return total
