"""Test bootstrap: force an 8-device virtual CPU mesh.

Mirrors the reference's `local[4]` Spark masters in unit tests (SURVEY.md §4): multi-device
behaviour (data sharding, collective insertion) is exercised on host CPU devices; real-TPU
runs happen in bench.py / __graft_entry__.py.

The CPU mesh is the tests' explicit choice: XLA_FLAGS is set before the CPU client is
created and the platform is pinned via jax.config before any backend starts.  The
persistent compilation cache stays off in the test process (runs must not depend on
what an earlier run compiled); tests of the cache itself spawn children that turn it on.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import signal  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): fail the test with TimeoutError if it runs "
        "longer — SIGALRM-based (no pytest-timeout in this image), so a "
        "hung drain or stuck subprocess can't stall the tier-1 run past "
        "its budget")
    config.addinivalue_line(
        "markers",
        "slow: throughput sweeps / long benchmarks excluded from the "
        "tier-1 run (`-m 'not slow'`)")
    config.addinivalue_line(
        "markers",
        "replicas: multi-process replica failover tests (SIGKILL + "
        "reclaim); carry a default 300 s SIGALRM budget so a wedged "
        "replica subprocess cannot stall tier-1")
    config.addinivalue_line(
        "markers",
        "multichip: sharded multi-chip serving tests; self-spawn a "
        "subprocess under XLA_FLAGS=--xla_force_host_platform_device_"
        "count=N so the mesh path runs on CPU-only containers, with a "
        "default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "wire: binary wire / shm-lane / HTTP-gateway tests (shared-memory "
        "segments + curl subprocesses); carry a default 120 s SIGALRM "
        "budget so a wedged gateway or leaked segment cannot stall tier-1")
    config.addinivalue_line(
        "markers",
        "autoscale: closed-loop autoscaler / load-balancer tests (engine "
        "fleets, front-door sockets; the chaos A/B additionally carries "
        "`slow` because it spawns live replica subprocesses); default "
        "300 s SIGALRM budget so a wedged fleet cannot stall tier-1")
    config.addinivalue_line(
        "markers",
        "coldstart: zero-cold-start tests (AOT warm-up, persistent XLA "
        "compilation cache, mmap weight store); the spawn-twice test "
        "forks fresh interpreters that re-import jax and compile, so "
        "they carry a default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "generation: continuous-batching generation tests (token-level "
        "scheduler, step-wise decode, streaming partials); they compile "
        "per-bucket decode programs and drive live engines, so they "
        "carry a default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "quant: fused-dequant quantized-predict tests (Pallas kernel "
        "parity vs the XLA oracle, int4/int8 calibration + packing, "
        "quantized weight-store round-trips, warm quantized serving); "
        "they run the kernels in interpret mode on CPU and compile "
        "small programs, so they carry a default 120 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "forensics: incident flight-recorder / resource-ledger / "
        "on-demand-profiling tests (PR 15); the capture e2e forks real "
        "manager processes, so they carry a default 300 s SIGALRM "
        "budget")
    config.addinivalue_line(
        "markers",
        "tracing: fleet-wide distributed-tracing tests (span propagation "
        "across LB/gateway/engine, spool merge, SLO attribution); the "
        "cross-process ones spawn replica subprocesses and long-poll "
        "through the front door, so they carry a default 120 s SIGALRM "
        "budget (subprocess-heavy ones raise it with an explicit "
        "timeout mark)")
    config.addinivalue_line(
        "markers",
        "rollout: versioned-rollout / canary / auto-rollback tests "
        "(PR 16); the acceptance tests fork real manager supervisors, "
        "publish registry versions and wait out canary dwell windows, so "
        "they carry a default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "overload: overload-armor tests (PR 17: tenant admission, "
        "priority shedding, brownout ladder, retry budget); the "
        "acceptance test floods a live mixed-priority fleet through the "
        "gateway, so they carry a default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "kvcache: paged-KV tests (PR 18: block pool, prefix sharing, "
        "int8 KV lanes, paged attention kernel parity); they compile "
        "paged prefill/decode programs and run the kernel in interpret "
        "mode on CPU, so they carry a default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "metering: usage-metering / attribution tests (PR 19: "
        "tenant/model-labelled series, usage journal, per-tenant SLO "
        "views); the acceptance test forks a real 2-replica deployment "
        "behind the LB, so they carry a default 300 s SIGALRM budget")
    config.addinivalue_line(
        "markers",
        "resume: generation-continuity tests (PR 20: checkpointed decode "
        "state, crash-resumable generations); the chaos acceptance "
        "SIGKILLs a live replica mid-decode and waits for the survivor's "
        "reclaim + token-exact resume, so they carry a default 300 s "
        "SIGALRM budget")


# replica-failover tests fork full serving processes (jax import + model
# build each) and then wait on kill/reclaim cycles: the default budget when
# no explicit `timeout` mark is given.  multichip tests fork a fresh
# interpreter that re-imports jax and compiles sharded programs — same class
# of cost, same budget.
REPLICAS_DEFAULT_TIMEOUT_S = 300.0
MULTICHIP_DEFAULT_TIMEOUT_S = 300.0
WIRE_DEFAULT_TIMEOUT_S = 120.0
AUTOSCALE_DEFAULT_TIMEOUT_S = 300.0
COLDSTART_DEFAULT_TIMEOUT_S = 300.0
GENERATION_DEFAULT_TIMEOUT_S = 300.0
TRACING_DEFAULT_TIMEOUT_S = 120.0
QUANT_DEFAULT_TIMEOUT_S = 120.0
FORENSICS_DEFAULT_TIMEOUT_S = 300.0
ROLLOUT_DEFAULT_TIMEOUT_S = 300.0
OVERLOAD_DEFAULT_TIMEOUT_S = 300.0
KVCACHE_DEFAULT_TIMEOUT_S = 300.0
METERING_DEFAULT_TIMEOUT_S = 300.0
RESUME_DEFAULT_TIMEOUT_S = 300.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Per-test wall-clock cap for the subprocess-based chaos/preemption/
    serving tests.  SIGALRM interrupts blocking syscalls (subprocess waits,
    socket reads) on the main thread, which is exactly where pytest runs the
    test body; platforms without SIGALRM just skip the guard."""
    marker = item.get_closest_marker("timeout")
    if not hasattr(signal, "SIGALRM"):
        return (yield)
    if marker is None:
        # the `replicas`/`multichip` marks imply a budget of their own:
        # multi-process tests must never hang tier-1 even without an
        # explicit mark
        if item.get_closest_marker("replicas") is not None:
            seconds = REPLICAS_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("multichip") is not None:
            seconds = MULTICHIP_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("wire") is not None:
            seconds = WIRE_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("autoscale") is not None:
            seconds = AUTOSCALE_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("coldstart") is not None:
            seconds = COLDSTART_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("generation") is not None:
            seconds = GENERATION_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("tracing") is not None:
            seconds = TRACING_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("quant") is not None:
            seconds = QUANT_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("forensics") is not None:
            seconds = FORENSICS_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("rollout") is not None:
            seconds = ROLLOUT_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("overload") is not None:
            seconds = OVERLOAD_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("kvcache") is not None:
            seconds = KVCACHE_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("metering") is not None:
            seconds = METERING_DEFAULT_TIMEOUT_S
        elif item.get_closest_marker("resume") is not None:
            seconds = RESUME_DEFAULT_TIMEOUT_S
        else:
            return (yield)
    else:
        seconds = float(marker.args[0]) if marker.args \
            else float(marker.kwargs.get("seconds", 60))

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded its {seconds:g}s timeout mark")

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.fixture(scope="session")
def _session_ctx():
    from analytics_zoo_tpu.common.context import init_context
    return init_context(seed=42)


@pytest.fixture()
def ctx(_session_ctx):
    # Always hand out the CURRENT global context (a test may have replaced it
    # via init_context), re-seeded so each test sees a deterministic rng
    # stream regardless of which (or how many) other tests ran before it.
    from analytics_zoo_tpu.common.context import get_context
    c = get_context()
    c.set_seed(42)
    return c


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
