"""The generate programs read their matmul weights in the MXU's operand
type, rounded once at load (PR 31).

On a TPU a float32 matmul at jax's default precision is one bfloat16 MXU
pass: the backend rounds both operands itself, in every call.
``TransformerLM.matmul_operands`` makes the rounded weights once and
``ContinuousBatcher._params`` hands every program that tree.  Checked here on
the CPU with a tiny ``TransformerLM`` and the rule forced to bfloat16:

- every step-wise path over the operand tree gives the logits of a forward
  written out below with both operands of every weight matmul rounded to
  bfloat16 and accumulated in float32 (the MXU pass's arithmetic), and the
  scheduler serves that forward's argmax;
- the operand tree shares every leaf that is not a matmul weight with the
  float32 tree and holds no float32 weight;
- on the CPU's own rule nothing is copied: ``_params()`` IS the model's tree;
- a replaced ``model._params`` rebuilds the form once and drops the old
  copies; a model without the method is served with its parameters as they are;
- ``pdecode`` lowered over the operand tree converts no weight-shaped tensor,
  and compiled for a described v5e neither converts nor copies one.
"""

import gc
import re
import weakref

import numpy as np
import pytest

pytestmark = pytest.mark.kvcache

V, H, NH, L, MAXLEN = 64, 32, 2, 2, 64
PATHS = ("init_decode", "decode_step", "prefill_paged", "decode_paged",
         "prefill_shared_paged")


# -- helpers ------------------------------------------------------------------

def _lm(cls=None, **kw):
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    geo = dict(vocab_size=V, hidden=H, n_head=NH, n_layers=L, max_len=MAXLEN,
               initializer_range=0.2)
    geo.update(kw)
    return (cls or TransformerLM)(**geo)


def _params(lm, seed=5):
    import jax
    p = lm.build(jax.random.PRNGKey(seed))
    # biases and LayerNorm offsets that are not zero, so that a dropped one shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def jitter(a):
        return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)

    for blk in p["blocks"]:
        for name in ("qkv", "proj", "fc1", "fc2", "ln1", "ln2"):
            blk[name]["b"] = jitter(blk[name]["b"])
    return p


def _mxu(x, w):
    """One bfloat16 MXU pass over float32 operands: both rounded to
    bfloat16 (to nearest even), the products exact, summed in float32."""
    import jax.numpy as jnp

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    return jnp.matmul(rounded(x), rounded(w), precision="highest")


def _forward(params, ids):
    """The plain forward of ``ids`` (T,) over the FLOAT32 tree, written out:
    (T, V) logits; every weight matmul through ``_mxu``, the rest float32.
    Rounding to bfloat16 is a step function: an activation one float32 ulp
    off can round the other way and move a logit by 1e-3.  So the float32
    pieces between the matmuls are spelled as the model spells them, and
    only a matmul's order of summation can differ."""
    import jax
    import jax.numpy as jnp

    def ln(p, x):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["b"]

    ids = jnp.asarray(ids, jnp.int32)
    T, hd = ids.shape[0], H // NH
    causal = jnp.tril(jnp.ones((T, T), bool))
    x = params["embed"][ids] + params["pos"][:T]
    for blk in params["blocks"]:
        qkv = _mxu(ln(blk["ln1"], x), blk["qkv"]["W"]) + blk["qkv"]["b"]
        q, k, v = (qkv[:, i * H:(i + 1) * H].reshape(T, NH, hd)
                   for i in range(3))
        att = jnp.einsum("qhd,khd->hqk", q, k) * (1.0 / np.sqrt(hd))
        att = jax.nn.softmax(jnp.where(causal, att, -1e30), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", att, v).reshape(T, H)
        x = x + (_mxu(o, blk["proj"]["W"]) + blk["proj"]["b"])
        h = _mxu(ln(blk["ln2"], x), blk["fc1"]["W"]) + blk["fc1"]["b"]
        x = x + (_mxu(jax.nn.gelu(h), blk["fc2"]["W"]) + blk["fc2"]["b"])
    return np.asarray(_mxu(ln(params["ln_f"], x), params["embed"].T))


def _rollout(params, prompt, n):
    """Greedy tokens of ``_forward`` after ``prompt`` and the logits each was
    taken from; no near-tie among them, or the comparison would be luck."""
    ids, logits = list(prompt), []
    for _ in range(n):
        row = _forward(params, np.asarray(ids))[-1]
        top = np.sort(row)[-2:]
        assert top[1] - top[0] > 1e-3, "near-tie in the reference: reseed"
        logits.append(row)
        ids.append(int(row.argmax()))
    return ids[len(prompt):], np.stack(logits)


def _close(got, want, what):
    """Float32 sums in another order: 1e-5.  (A difference of 1e-3 to 1e-2
    in one row is an activation that rounded the other way, see ``_forward``:
    these seeds have none; a new jax that sums otherwise may need others.)"""
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5,
                               err_msg=what)


def _force(monkeypatch):
    """The rule as a TPU at default precision reads it (a test steers it; the
    program has no option for it)."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "matmul_operand_dtype",
                        lambda: jnp.bfloat16)


def _batcher(lm, params, **kw):
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams)
    gen = dict(paged=True, block_len=4, max_active_slots=4,
               max_prompt_len=16, max_tokens=6, bucket_lens=[32],
               prefill_buckets=[8, 16], decode_quantum=2)
    gen.update(kw)
    im = InferenceModel().do_load_model(lm, params, {})
    return im, ContinuousBatcher(im, GenerationParams(**gen))


def _prompts(n=5):
    g = np.random.default_rng(31)
    return [g.integers(1, V, int(g.integers(2, 17))).astype(np.int32)
            for _ in range(n)]


def _serve(b, prompts, budget=5, tag="r"):
    from analytics_zoo_tpu.serving.generate import GenRequest
    for i, prompt in enumerate(prompts):
        assert b.submit(GenRequest(f"{tag}{i}", prompt, max_tokens=budget))
    done = {}
    for _ in range(2000):
        for ev in b.step():
            assert ev.kind not in ("quarantine", "shed"), ev
            if ev.kind == "finish":
                done[ev.rid] = list(ev.tokens)
        if len(done) == len(prompts):
            return [done[f"{tag}{i}"] for i in range(len(prompts))]
    raise AssertionError(f"stalled at {len(done)} of {len(prompts)}")


# -- (1) the arithmetic -------------------------------------------------------

@pytest.mark.parametrize("path", PATHS)
def test_every_stepwise_path_is_the_mxu_pass_over_rounded_operands(path):
    import jax
    import jax.numpy as jnp
    lm = _lm()
    params = _params(lm)
    ops = lm.matmul_operands(params, jnp.bfloat16)
    g = np.random.default_rng(3)
    B, P, bl, ntab = 2, 8, 4, 8
    lens = np.asarray((5, 8), np.int32)
    prompt = g.integers(1, V, (B, P)).astype(np.int32)
    rows = [prompt[i, :lens[i]] for i in range(B)]
    steps = 4
    toks, want = zip(*(_rollout(params, r, 1 + steps) for r in rows))
    toks, want = np.asarray(toks), np.stack(want)        # (B, 1+steps[, V])
    tables = 1 + np.arange(B * ntab, dtype=np.int32).reshape(B, ntab)
    slots = np.arange(B, dtype=np.int32)
    pools = jax.device_put(lm.init_paged_pools(1 + B * ntab, bl, B))

    if path in ("init_decode", "decode_step"):
        state, logits0 = lm.init_decode(ops, prompt, lens,
                                        cache_len=ntab * bl)
        _close(logits0, want[:, 0], "init_decode")
        for s in range(steps if path == "decode_step" else 0):
            logits, state = lm.decode_step(ops, state, toks[:, s])
            _close(logits, want[:, 1 + s], f"decode_step {s}")
    elif path in ("prefill_paged", "decode_paged"):
        pstate, logits0 = lm.prefill_paged(ops, pools, prompt, lens,
                                           tables[:, :P // bl], slots,
                                           block_len=bl)
        _close(logits0, want[:, 0], "prefill_paged")
        pos = lens
        for s in range(steps if path == "decode_paged" else 0):
            logits, pstate = lm.decode_paged(ops, pstate, tables, pos,
                                             toks[:, s], block_len=bl,
                                             impl="xla")
            _close(logits, want[:, 1 + s], f"decode_paged {s}")
            pos = pos + 1
    else:
        # one block of each row is a prefix already in the pool; the rest
        # of the row is the suffix that runs through the stack
        pstate, _ = lm.prefill_paged(ops, pools, prompt[:, :bl],
                                     np.full((B,), bl, np.int32),
                                     tables[:, :1], slots, block_len=bl)
        pstate, logits0 = lm.prefill_shared_paged(
            ops, pstate, prompt[:, bl:], lens - bl,
            np.full((B,), bl, np.int32), tables[:, :1], tables[:, 1:2],
            slots, block_len=bl)
        _close(logits0, want[:, 0], "prefill_shared_paged")


def test_the_scheduler_serves_the_rounded_forwards_argmax(monkeypatch):
    import jax.numpy as jnp
    _force(monkeypatch)
    lm = _lm()
    params = _params(lm)
    im, b = _batcher(lm, params)
    assert b._params()["head"].dtype == jnp.bfloat16
    prompts = _prompts()
    assert b.warm()["failed"] == 0
    want = [_rollout(params, p, 5)[0] for p in prompts]
    assert _serve(b, prompts) == want
    # the float32 tree the model holds was not touched
    assert im._params is params
    assert all(blk["qkv"]["W"].dtype == jnp.float32
               for blk in im._params["blocks"])


# -- (2) the tree -------------------------------------------------------------

def test_operand_tree_shares_what_is_not_a_matmul_weight():
    import jax
    import jax.numpy as jnp
    lm = _lm()
    params = _params(lm)
    ops = lm.matmul_operands(params, jnp.bfloat16)
    assert ops["embed"] is params["embed"] and ops["pos"] is params["pos"]
    assert ops["ln_f"]["g"] is params["ln_f"]["g"]
    assert ops["ln_f"]["b"] is params["ln_f"]["b"]
    for blk, src in zip(ops["blocks"], params["blocks"]):
        assert set(blk) == set(src)
        for name in ("ln1", "ln2"):
            assert blk[name]["g"] is src[name]["g"]
            assert blk[name]["b"] is src[name]["b"]
        for name in ("qkv", "proj", "fc1", "fc2"):
            assert blk[name]["b"] is src[name]["b"]
            assert blk[name]["W"].dtype == jnp.bfloat16
            assert blk[name]["W"].shape == src[name]["W"].shape
            np.testing.assert_array_equal(
                np.asarray(blk[name]["W"].astype(jnp.float32)),
                np.asarray(src[name]["W"].astype(jnp.bfloat16)
                           .astype(jnp.float32)))
    assert ops["head"].dtype == jnp.bfloat16 and ops["head"].shape == (H, V)
    np.testing.assert_array_equal(
        np.asarray(ops["head"].astype(jnp.float32)),
        np.asarray(params["embed"].T.astype(jnp.bfloat16)
                   .astype(jnp.float32)))
    f32_2d = [leaf for leaf in jax.tree.leaves(ops)
              if leaf.ndim == 2 and leaf.dtype == jnp.float32]
    assert {id(a) for a in f32_2d} == {id(params["embed"]),
                                      id(params["pos"])}
    # the float32 tree is as it was
    assert params["blocks"][0]["qkv"]["W"].dtype == jnp.float32
    assert "head" not in params
    # nothing to round: the tree itself; a weight that is not float32 stays
    assert lm.matmul_operands(params, None) is params
    again = lm.matmul_operands(ops, jnp.bfloat16)
    assert again["blocks"][1]["fc1"]["W"] is ops["blocks"][1]["fc1"]["W"]


def test_operand_copies_keep_their_sharding():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    lm = _lm()
    params = _params(lm)
    col, row = NamedSharding(mesh, P(None, "model")), \
        NamedSharding(mesh, P("model", None))
    for blk in params["blocks"]:
        blk["qkv"]["W"] = jax.device_put(blk["qkv"]["W"], col)
        blk["fc2"]["W"] = jax.device_put(blk["fc2"]["W"], row)
    params["embed"] = jax.device_put(params["embed"], row)
    ops = lm.matmul_operands(params, jnp.bfloat16)
    for blk in ops["blocks"]:
        assert blk["qkv"]["W"].sharding.is_equivalent_to(col, 2)
        assert blk["fc2"]["W"].sharding.is_equivalent_to(row, 2)
    # the head is the embedding transposed, and so is its sharding
    assert ops["head"].sharding.is_equivalent_to(col, 2)
    ids = np.arange(1, 7)[None]
    _close(lm.call(ops, ids)[0], _forward(params, ids[0]), "sharded call")


# -- (3) the rule -------------------------------------------------------------

@pytest.mark.parametrize("tpu,precision,want", [
    (False, None, None), (False, "bfloat16", None),
    (True, None, "bfloat16"), (True, "default", "bfloat16"),
    (True, "bfloat16", "bfloat16"), (True, "BF16_BF16_F32", "bfloat16"),
    (True, "highest", None), (True, "float32", None),
    (True, "tensorfloat32", None), (True, "BF16_BF16_F32_X3", None)])
def test_the_rule_is_what_the_backends_matmul_does(monkeypatch, tpu,
                                                   precision, want):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: tpu)
    with jax.default_matmul_precision(precision):
        got = dispatch.matmul_operand_dtype()
    assert got is (None if want is None else jnp.bfloat16)


def test_on_the_cpu_the_operand_form_is_the_float32_tree():
    lm = _lm()
    params = _params(lm)
    im, b = _batcher(lm, params)
    assert b._params() is im._params is params
    assert b._params() is params
    s = b.stats()
    assert (s["operand_builds"], s["operand_bytes"]) == (1, 0)
    prompts = _prompts(3)
    want = [list(np.asarray(lm.generate(params, p[None], max_tokens=5))[0])
            for p in prompts]
    assert _serve(b, prompts) == want


# -- (4) the hand-over --------------------------------------------------------

def test_a_replaced_tree_rebuilds_the_form_once(monkeypatch):
    import jax
    import jax.numpy as jnp
    _force(monkeypatch)
    lm = _lm()
    params = _params(lm)
    # no prefix index: K/V it holds from the old weights would be served on
    # (a replaced tree does not clear it, before this change or after)
    im, b = _batcher(lm, params, prefix_cache=False)
    first = b._params()
    assert b._params() is first and b.stats()["operand_builds"] == 1
    copies = 2 * (L * 12 * H * H + V * H)
    assert b.stats()["operand_bytes"] == copies
    old = weakref.ref(first["blocks"][0]["qkv"]["W"])
    prompts = _prompts(3)
    assert _serve(b, prompts, tag="a") \
        == [_rollout(params, p, 5)[0] for p in prompts]
    # a weight load: InferenceModel replaces its tree
    fresh = _params(lm, seed=9)
    im._params = fresh
    second = b._params()
    assert second is not first and b._params() is second
    s = b.stats()
    assert (s["operand_builds"], s["operand_bytes"]) == (2, copies)
    assert second["embed"] is fresh["embed"]
    assert second["blocks"][0]["qkv"]["W"].dtype == jnp.bfloat16
    del first
    gc.collect()
    assert old() is None, "the old operand copies are still held"
    # the compiled programs serve the new weights (same shapes, no recompile)
    compiles = b.compiles
    assert _serve(b, prompts, tag="b") \
        == [_rollout(fresh, p, 5)[0] for p in prompts]
    assert b.compiles == compiles
    assert jax.tree.structure(second) == jax.tree.structure(b._params())


def test_a_changed_rule_rebuilds_the_form_and_its_programs(monkeypatch):
    """The rule is read at every call, not once: a user who turns jax to
    ``highest`` under a live batcher is served float32 operands by programs
    compiled for them, and bfloat16 ones again after turning it back."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import dispatch
    rule = [jnp.bfloat16]
    monkeypatch.setattr(dispatch, "matmul_operand_dtype", lambda: rule[0])
    lm = _lm()
    params = _params(lm)
    im, b = _batcher(lm, params, prefix_cache=False)
    prompts = _prompts(3)
    rounded = [_rollout(params, p, 5)[0] for p in prompts]
    assert _serve(b, prompts, tag="a") == rounded
    first, compiled = b._params(), b.compiles
    assert first["blocks"][0]["qkv"]["W"].dtype == jnp.bfloat16
    rule[0] = None
    assert b._params() is params
    s = b.stats()
    assert (s["operand_builds"], s["operand_bytes"]) == (2, 0)
    assert b.program_stats()["count"] == 0, "bfloat16 programs were kept"
    exact = [list(np.asarray(lm.generate(params, p[None], max_tokens=5))[0])
             for p in prompts]
    assert _serve(b, prompts, tag="b") == exact
    assert b.compiles > compiled
    rule[0] = jnp.bfloat16
    again = b._params()
    assert again is not first and "head" in again
    assert b.stats()["operand_builds"] == 3
    assert _serve(b, prompts, tag="c") == rounded
    # turned with requests in flight: the next call compiles for the form
    # it is handed; no call meets an executable of the other one
    from analytics_zoo_tpu.serving.generate import GenRequest
    for i, prompt in enumerate(prompts):
        assert b.submit(GenRequest(f"d{i}", prompt, max_tokens=5))
    kinds = [ev.kind for ev in b.step()]
    rule[0] = None
    for _ in range(200):
        kinds += [ev.kind for ev in b.step()]
    assert kinds.count("finish") == len(prompts)
    assert not {"quarantine", "shed"} & set(kinds)
    assert b.stats()["operand_builds"] == 4


def test_the_resource_ledger_counts_the_operand_copies(monkeypatch):
    """One place owns resident bytes: the ``weights`` component (the
    ``serving_hbm_bytes`` gauge, ``/healthz``'s ``resources``) holds the
    float32 tree AND the copies the scheduler keeps beside it."""
    from analytics_zoo_tpu.inference.quantize import weight_bytes
    from analytics_zoo_tpu.inference.resources import ResourceLedger
    lm = _lm()
    params = _params(lm)
    tree = int(weight_bytes(params))
    copies = 2 * (L * 12 * H * H + V * H)
    im, b = _batcher(lm, params)
    led = ResourceLedger(im, b)
    assert led.weights_bytes() == tree           # before any program
    b._params()
    assert led.weights_bytes() == tree           # the CPU's rule: no copy
    assert led.doc()["weights_bytes"] == tree
    _force(monkeypatch)
    b._params()
    assert b.stats()["operand_bytes"] == copies
    assert led.weights_bytes() == led.hbm_bytes("weights") == tree + copies
    doc = led.doc()
    assert doc["weights_bytes"] == tree + copies
    assert doc["total_bytes"] >= tree + copies + doc["kv_state_bytes"]
    assert ResourceLedger(im).weights_bytes() == tree    # no scheduler


def test_both_threads_get_one_form(monkeypatch):
    import threading
    _force(monkeypatch)
    lm = _lm()
    _, b = _batcher(lm, _params(lm))
    b._operand_src, b._operand_form = (None, None), None   # as before any call
    b.operand_builds = 0
    got, gate = [], threading.Barrier(4)

    def ask():
        gate.wait()
        got.append(b._params())

    threads = [threading.Thread(target=ask) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(g is got[0] for g in got) and b.operand_builds == 1


def test_a_model_without_the_method_is_served_as_before(monkeypatch):
    from analytics_zoo_tpu.models.textmodels import TransformerLM
    _force(monkeypatch)

    class Bare(TransformerLM):
        """The contract's other methods, no ``matmul_operands``."""
        def __getattribute__(self, name):
            if name == "matmul_operands":
                raise AttributeError(name)
            return super().__getattribute__(name)

    lm = _lm(Bare)
    assert not hasattr(lm, "matmul_operands")
    params = _params(lm)
    im, b = _batcher(lm, params)
    assert b._params() is params
    s = b.stats()
    assert (s["operand_builds"], s["operand_bytes"]) == (0, 0)
    prompts = _prompts(3)
    want = [list(np.asarray(
        _lm().generate(params, p[None], max_tokens=5))[0]) for p in prompts]
    assert _serve(b, prompts) == want


# -- (5) the program ----------------------------------------------------------

WEIGHT_SHAPES = {(H, 3 * H), (H, H), (H, 4 * H), (4 * H, H), (H, V), (V, H)}


def test_pdecode_over_the_operand_tree_converts_no_weight(monkeypatch):
    _force(monkeypatch)
    lm = _lm()
    # 8 slots: an activation is (8, .), no weight's shape
    _, b = _batcher(lm, _params(lm), max_active_slots=8)
    lane = b._lanes[0]
    fn, args = b._lowering(("pdecode", lane.bucket), lane)
    text = fn.lower(*args).as_text()
    converts = re.findall(r"stablehlo\.convert[^\n]*-> tensor<([0-9x]+)x\w+>",
                          text)
    assert converts, "the activations are rounded in the program"
    shapes = {tuple(int(d) for d in c.split("x")) for c in converts}
    assert not shapes & WEIGHT_SHAPES, shapes & WEIGHT_SHAPES
    # every weight matmul takes bfloat16 on both sides and gives float32
    dots = re.findall(r"stablehlo\.dot_general[^\n]*: \(([^)]*)\) -> (\S+)",
                      text)
    weight_dots = [(ins, out) for ins, out in dots
                   if any(f"tensor<{'x'.join(map(str, s))}xbf16>" in ins
                          for s in WEIGHT_SHAPES)]
    assert len(weight_dots) == 4 * L + 1
    for ins, out in weight_dots:
        assert ins.count("xbf16>") == 2 and out.endswith("xf32>"), (ins, out)


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip for compile-only checks."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_compiled_for_a_v5e_pdecode_neither_converts_nor_copies_a_weight(
        one_chip, monkeypatch):
    """gpt2-large's widths at two layers, compiled (not run) for the chip:
    the optimised program holds no ``convert`` and no ``transpose`` of a
    weight's shape, and every weight stays bfloat16 through its prefetch."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import dispatch
    from analytics_zoo_tpu.serving.generate import (ContinuousBatcher,
                                                    GenerationParams)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    Hh, Vv, A, bl = 1280, 50257, 8, 16
    lm = _lm(vocab_size=Vv, hidden=Hh, n_head=20, n_layers=2, max_len=1024)
    shapes = jax.eval_shape(
        lambda k: lm.matmul_operands(lm.build(k), jnp.bfloat16),
        jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    b = ContinuousBatcher.__new__(ContinuousBatcher)
    b.inner, b._programs = lm, {}
    b.gen = GenerationParams(paged=True, block_len=bl, max_active_slots=A,
                             decode_quantum=4)
    pdecode = b._paged_fns()[2]
    ntab = 1024 // bl
    pools = jax.eval_shape(
        lambda: lm.init_paged_pools(A * ntab + 1, bl, A, "off"))
    i32 = jax.ShapeDtypeStruct
    hlo = pdecode.lower(
        on_chip(shapes), on_chip(pools),
        i32((A, ntab), np.int32, sharding=one_chip),
        i32((A,), np.int32, sharding=one_chip),
        i32((A,), np.int32, sharding=one_chip)).compile().as_text()
    assert "custom-call" in hlo, "the Pallas kernel is not in the program"

    def dims(*pairs):
        return "|".join(f"{a},{c}" for a, c in pairs)

    matmul = [(Hh, 3 * Hh), (Hh, Hh), (Hh, 4 * Hh), (4 * Hh, Hh), (Hh, Vv)]
    made = re.findall(rf"= (\w+)\[(?:{dims(*matmul, (Vv, Hh))})\]\S* "
                      r"(convert|transpose|copy)\(", hlo)
    assert not made, made
    # float32 at a weight's size is the embedding (the gather's) alone
    assert not re.findall(rf"= f32\[(?:{dims(*matmul)})\]", hlo), \
        "a float32 matmul weight in the program"


def test_compiled_for_a_v5e_the_grouped_page_kernel_lowers_at_the_cells_widths(
        one_chip):
    """``WindowMoELM``'s full-layer read at the ``smallthinker-21b-a3b`` cell's
    widths (16 rows, 4 key heads of 7 queries, a pool of 2,049 blocks of
    (4, 128, 128) bfloat16, a lane of 128 entries), compiled (not run) for the
    chip: Mosaic takes the kernel, and it needs no temporary beside its
    operands."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import paged_attention as paged

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = on_chip((2049, 4, 128, 128), jnp.bfloat16)
    compiled = jax.jit(paged.grouped_paged_attention).lower(
        on_chip((16, 4, 7, 128), jnp.float32), pool, pool,
        on_chip((16, 128), jnp.int32), on_chip((16,), jnp.int32)).compile()
    assert "grouped_paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_compiled_for_a_v5e_the_grouped_page_kernel_reads_key_pairs(one_chip):
    """``SSMHybridLM``'s page read at the ``phi-4-mini-flash`` cell's widths
    (32 rows; 10 key PAIRS, each one 128-wide key head of the pages, 4 query
    rows a pair: ``[q1 | 0]`` and ``[0 | q2]`` of its two differential
    heads; a pool of 1,025 blocks of (10, 128, 128) bfloat16, a lane of 32
    entries), compiled (not run) for the chip: Mosaic takes the kernel, and
    it needs no temporary beside its operands."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import paged_attention as paged

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = on_chip((1025, 10, 128, 128), jnp.bfloat16)
    compiled = jax.jit(paged.grouped_paged_attention).lower(
        on_chip((32, 10, 4, 128), jnp.float32), pool, pool,
        on_chip((32, 32), jnp.int32), on_chip((32,), jnp.int32)).compile()
    assert "grouped_paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("pairs,experts,hidden,width,act", [
    (96, 64, 2560, 768, "relu"), (128, 16, 6144, 2048, "silu")],
    ids=["smallthinker_docmix", "glm5_longdoc"])
def test_compiled_for_a_v5e_the_expert_kernel_lowers_at_the_cells_widths(
        one_chip, pairs, experts, hidden, width, act):
    """A decode step's routed experts at the two MoE cells' widths (16 rows
    x top-6 over 64 held experts of 2560 x 768; 16 rows x top-8 over 16
    held experts of 6144 x 2048), compiled (not run) for the chip: Mosaic
    takes ``ops/expert_matmul``'s kernel in the tile its shapes give (256
    and 128 lanes), and no weight is copied beside it."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import expert_matmul

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = on_chip((experts, hidden, width), jnp.bfloat16)
    compiled = jax.jit(lambda x, a, b, c, s: expert_matmul.grouped_expert_ffn(
        x, a, b, c, s, act=getattr(jax.nn, act))).lower(
        on_chip((pairs, hidden), jnp.bfloat16), w, w,
        on_chip((experts, width, hidden), jnp.bfloat16),
        on_chip((experts,), jnp.int32)).compile()
    assert "grouped_expert_ffn" in compiled.as_text()
    assert expert_matmul._f_tile(hidden, width, 2) == \
        {768: 256, 2048: 128}[width]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
