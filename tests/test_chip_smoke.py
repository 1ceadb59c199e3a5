"""chip_smoke.py off the chip, and the no-hidden-fallback rules it rests on.

The script only reaches exit 0 on a TPU; here its refusal is checked in a
child, and its leg functions run in-process at toy width with the kernels
chosen EXPLICITLY (``impl="interpret"``) — the same code the driver runs at
full width on the chip.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = {
    "train": {"depth": 18, "image": 32, "classes": 10, "batch_per_chip": 1,
              "single_steps": 2, "scanned_calls": 1, "steps_per_call": 2},
    "predict": {"depth": 18, "image": 32, "classes": 10, "max_batch": 8,
                "records": 10, "http_records": 2},
    "generate": {"vocab": 97, "hidden": 32, "heads": 4, "layers": 1,
                 "max_len": 64, "slots": 2, "max_tokens": 12,
                 "prompt_lens": [3, 11], "prefill_buckets": [16],
                 "block_len": 8, "http_requests": 1},
    # one table group, then three with the last one half empty
    "kernels": {"paged": [{"rows": 4, "heads": 4, "head_dim": 8,
                           "block_len": 8, "n_table": 4,
                           "lengths": [32, 17, 9, 1]},
                          {"rows": 3, "heads": 4, "head_dim": 8,
                           "block_len": 8, "n_table": 40,
                           "lengths": [320, 129, 1]}],
                "flash": {"batch": 1, "heads": 2, "seq": 128, "head_dim": 16},
                "matmul": [(16, 256, 40)]},
}


@pytest.fixture(autouse=True)
def _restore_dtype_policy():
    """The legs set the process-global dtype policy, as a user would; the
    tests that run after these must not inherit it."""
    from analytics_zoo_tpu.common import dtypes
    saved = dtypes.compute_dtype(), dtypes.param_dtype()
    yield
    dtypes.set_policy(*saved)


def test_refuses_off_the_chip():
    """JAX_PLATFORMS=cpu: one refusal line on stderr, non-zero exit, and
    no result line on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=120, cwd=REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    refusal = [l for l in out.stderr.splitlines()
               if l.startswith("chip_smoke: refused")]
    assert len(refusal) == 1 and "not a TPU" in refusal[0], out.stderr[-500:]


def test_no_except_between_a_leg_and_the_exit_code():
    """Reviewable contract: main() runs the legs with no handler that
    could let a failed leg reach exit 0."""
    import ast
    import inspect
    main = ast.parse(inspect.getsource(chip_smoke.main)).body[0]
    for node in ast.walk(main):
        if isinstance(node, ast.Try):
            caught = [ast.unparse(h.type) if h.type else "bare"
                      for h in node.handlers]
            # the package-import refusal is the only handler in main
            assert caught in ([], ["ImportError"]), caught


def test_kernels_leg_interpreted():
    doc = chip_smoke.leg_kernels(TOY["kernels"], impl="interpret")
    assert set(doc["kernels"]) >= {
        "paged_attention_4x4x8x4", "paged_attention_int8_4x4x8x4",
        "paged_attention_3x4x8x40", "paged_attention_int8_3x4x8x40",
        "flash_fwd", "flash_bwd"}
    assert all(k["mosaic_calls"] == 0 for k in doc["kernels"].values())


def test_kernels_leg_fails_when_pallas_did_not_lower():
    """On CPU nothing lowers to Mosaic: asking the leg to prove compiled
    Pallas must fail, not pass on the reference."""
    with pytest.raises(Exception):
        chip_smoke.leg_kernels(TOY["kernels"], impl="pallas")


def test_train_leg_toy(ctx):
    doc = chip_smoke.leg_train(TOY["train"])
    assert doc["steps"] == 4 and doc["devices"] == 8
    assert doc["losses"][0] != doc["losses"][-1]


def test_predict_leg_toy(ctx, tmp_path):
    doc = chip_smoke.leg_predict(TOY["predict"], str(tmp_path))
    assert doc["values_out"] == doc["records_in"] == 12
    assert doc["quarantined"] == 0 and doc["shed"] == 0
    assert doc["warmup"]["state"] == "ready"


def test_generate_leg_toy(ctx):
    doc = chip_smoke.leg_generate(TOY["generate"], expect_mosaic=False)
    assert doc["requests"] == 3 and doc["prefix_hits"] >= 1
    assert doc["partials_streamed"] >= 3
    assert doc["decode_mosaic_calls"] == 0       # off the chip: reference


# -- the rules the smoke rests on ----------------------------------------------

def test_compile_cache_resolver(monkeypatch, tmp_path):
    from analytics_zoo_tpu.inference import aot
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_compile_cache")
    assert aot.compile_cache_dir() == fixed == aot.DEFAULT_COMPILE_CACHE_DIR
    assert aot.compile_cache_dir("/x/deploy") == "/x/deploy"
    assert aot.compile_cache_dir("off") is None
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for configured in (None, "/x/deploy", "off"):
        assert aot.compile_cache_dir(configured) == str(tmp_path)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_bootstrap_default_never_moves_an_earlier_choice(monkeypatch,
                                                         tmp_path):
    """ZooContext calls enable_persistent_cache() with no setting of its
    own: a deployment's directory, or its "off", must survive that."""
    import jax

    from analytics_zoo_tpu.inference import aot
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_enable_compilation_cache)
    try:
        assert aot.enable_persistent_cache(str(tmp_path)) == str(tmp_path)
        assert aot.enable_persistent_cache() == str(tmp_path)
        assert aot.enable_persistent_cache("off") is None
        assert not jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_enable_compilation_cache", saved[1])


def test_one_compile_cache_writer_in_the_tree():
    hits = []
    roots = [os.path.join(REPO, d) for d in ("analytics_zoo_tpu", "tools")]
    files = [os.path.join(REPO, f) for f in ("bench.py", "chip_smoke.py",
                                             "__graft_entry__.py")]
    for root in roots:
        for d, _, names in os.walk(root):
            files += [os.path.join(d, n) for n in names if n.endswith(".py")]
    writer = re.compile(r"""update\(\s*["']jax_compilation_cache_dir""")
    for path in files:
        with open(path) as f:
            hits += [path for line in f if writer.search(line)]
    assert [os.path.relpath(p, REPO) for p in hits] == [
        os.path.join("analytics_zoo_tpu", "inference", "aot.py")]


def test_peak_flops_unknown_device_raises():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from conv_ceiling import peak_flops

    class Dev:
        platform, device_kind = "cpu", "cpu"

    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        peak_flops(Dev())
    Dev.platform, Dev.device_kind = "tpu", "TPU v5 lite"
    assert peak_flops(Dev()) == 197e12


def test_flash_failure_raises_out_of_attention(monkeypatch):
    """A flash call that cannot trace is an error, not the XLA result."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops import attention, flash_attention

    def broken(*a, **k):
        raise RuntimeError("flash cannot trace")

    monkeypatch.setattr(flash_attention, "flash_attention", broken)
    q = jnp.ones((1, 2, 16, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="flash cannot trace"):
        attention.dot_product_attention(q, q, q, use_flash=True)
    with pytest.raises(RuntimeError, match="flash cannot trace"):
        attention.attention_bthd(q, q, q, use_flash=True)


def test_unknown_backend_is_an_error_not_the_reference(monkeypatch):
    import jax

    from analytics_zoo_tpu.ops import dispatch
    monkeypatch.setattr(jax, "default_backend", lambda: "mystery")
    with pytest.raises(RuntimeError, match="neither tpu nor cpu"):
        dispatch.resolve_impl(None)
    assert dispatch.resolve_impl("xla") == "xla"      # explicit still wins


def test_dryrun_multichip_takes_the_devices_it_is_given():
    import jax

    import __graft_entry__ as g
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        g.dryrun_multichip(64)
    assert len(jax.devices()) == 8       # and did not re-bootstrap jax
