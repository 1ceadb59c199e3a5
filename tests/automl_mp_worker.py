"""Multi-process AutoML worker (spawned by tests/test_automl_multiprocess.py).

Each process: 2 virtual CPU devices, jax.distributed bootstrap via
ZooConf.coordinator_address, then the context is REBUILT over
jax.local_devices() so every trial trains process-locally (no cross-process
collectives inside trials) — the MultiProcessSearchEngine contract.  Runs an
AutoTS search with distributed=True and prints one JSON line: the per-trial
metrics (identical on every process after the allgather), the best config,
how many trials THIS process executed, the wall-clock window its local
trials ran in, and the search wall time.

Run: python tests/automl_mp_worker.py <coordinator> <num_procs> <pid>
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# gloo CPU collectives only for REAL multi-process runs: this jaxlib's
# make_gloo_tcp_collectives binding requires a live DistributedRuntimeClient,
# so requesting gloo in a single-process worker (no jax.distributed
# bootstrap -> client is None) aborts CPU backend init outright
if len(sys.argv) > 2 and int(sys.argv[2]) > 1:
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


N_TRIALS = 4


def make_recipe():
    from analytics_zoo_tpu.automl.regression import Recipe
    from analytics_zoo_tpu.automl.search import Choice

    class _R(Recipe):
        n_trials = N_TRIALS

        def search_space(self, all_available_features=()):
            return {"model": "LSTM", "lstm_units": Choice([4, 8]),
                    "lr": Choice([0.01, 0.003]), "lookback": Choice([8]),
                    "dropout": Choice([0.0]), "epochs": Choice([2]),
                    "batch_size": Choice([32])}
    return _R()


def make_df(n=160):
    import pandas as pd
    g = np.random.default_rng(0)
    return pd.DataFrame({
        "datetime": pd.date_range("2020-01-01", periods=n, freq="h"),
        "value": np.sin(np.arange(n) / 12.0)
        + 0.05 * g.normal(size=n).astype(np.float32)})


def main():
    import time

    coord, nprocs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    from analytics_zoo_tpu.common.context import ZooConf, init_context
    if nprocs > 1:
        init_context(ZooConf(seed=42, coordinator_address=coord,
                             num_processes=nprocs, process_id=pid))
    # trials must be process-local: rebuild the context over local devices
    init_context(devices=jax.local_devices(), seed=42)

    from analytics_zoo_tpu.automl.regression import TimeSequencePredictor

    pred = TimeSequencePredictor(future_seq_len=1, recipe=make_recipe(),
                                 distributed=True)
    df = make_df()

    # count trials executed on THIS process: _train_one runs once per local
    # trial plus once for the best-config retrain
    calls = []
    orig_train_one = TimeSequencePredictor._train_one

    def counting(self, cfg, df_):
        t_start = time.time()
        try:
            return orig_train_one(self, cfg, df_)
        finally:
            calls.append((t_start, time.time()))

    TimeSequencePredictor._train_one = counting
    t0 = time.time()
    pipe = pred.fit(df, verbose=False)
    dt = time.time() - t0
    TimeSequencePredictor._train_one = orig_train_one
    engine_trials = [(t.config["lstm_units"], t.config["lr"],
                      round(t.metric, 6)) for t in pred._last_trials]
    print(json.dumps({
        "pid": pid,
        "trials": engine_trials,
        "best": {k: pipe.config[k] for k in ("lstm_units", "lr")},
        "local_trial_count": len(calls) - 1,   # minus the best retrain
        # epoch seconds (one host, one clock): first local trial's start,
        # last local trial's end; the retrain comes after the merge
        "trial_window": [calls[0][0], calls[-2][1]],
        "search_seconds": round(dt, 2),
    }))


if __name__ == "__main__":
    main()
