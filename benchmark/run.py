#!/usr/bin/env python3
"""benchmark/run.py — the one command of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the cell's
configuration (``configs/<config>.json``), its traffic (``traffic/<traffic>.json``),
the runner module the configuration names (``runners/<runner>.py``) and, for every
metric of the cell, its reader (``metrics/<metric>.json`` -> ``readers/<reader>.py``).
No cell, configuration or metric name appears in this file: a later PR adds any of
them as new files plus entries in ``BENCHMARK.json`` (see ``README.md``).

The last line of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``.  With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.
Exit code 2 with one line on stderr (and no result line) when jax's first device is
not a TPU, its kind is missing from ``peaks.json``, or the machine holds fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()        # set-up counts from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def refuse(message: str) -> "SystemExit":
    print(f"benchmark: refused: {message}", file=sys.stderr)
    return SystemExit(2)


def device_or_refuse(chips: int) -> dict:
    """The devices as jax reports them, plus this kind's row of ``peaks.json``.
    Anything but enough TPU chips of a known kind is a refusal."""
    try:
        import jax
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 — no backend at all is a refusal
        raise refuse(f"jax found no device ({type(e).__name__}: "
                     f"{str(e)[:200]})")
    first = devices[0]
    if first.platform != "tpu":
        raise refuse(f"jax's first device is {first.platform!r} "
                     f"({first.device_kind}), not a TPU")
    peaks = load_json(HERE, "peaks.json")
    if first.device_kind not in peaks:
        raise refuse(f"device kind {first.device_kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise refuse(f"the cell needs {chips} chip(s), jax found "
                     f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices), "peaks": peaks[first.device_kind]}


def load_cell(root: str, name: str):
    """``(manifest, cell, config, traffic)`` of the workload ``name``: the manifest's
    entry and the two data files it names."""
    manifest = load_json(root, "BENCHMARK.json")
    data = os.path.join(root, os.path.relpath(HERE, ROOT))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise refuse(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    return (manifest, cell,
            load_json(data, "configs", cell["config"] + ".json"),
            load_json(data, "traffic", cell["traffic"] + ".json"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_metric(name: str, facts: dict):
    """``metrics/<name>.json`` names a reader module and its arguments; a reader
    that finds nothing to read returns None and the metric is left out."""
    spec = load_json(HERE, "metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(facts, **spec.get("args", {}))


def main(argv=None, probe=device_or_refuse, root=ROOT) -> int:
    """``probe`` and ``root`` are for the rehearsals in ``tests/``: a root of
    their own holds a toy BENCHMARK.json with its configs/ and traffic/."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, cell, config, traffic = load_cell(root, args.workload)
    for path in (ROOT, HERE):       # the program, then runners/ and readers/
        if path not in sys.path:
            sys.path.insert(0, path)

    device = probe(cell["chips"])
    try:
        import analytics_zoo_tpu  # noqa: F401
        from analytics_zoo_tpu.inference import aot
    except ImportError as e:
        raise refuse(f"the program is not importable from {ROOT} ({e})")
    # $JAX_COMPILATION_CACHE_DIR if set, else the fixed <checkout>/.jax_compile_cache
    aot.enable_persistent_cache()

    runner = importlib.import_module("runners." + config["runner"])
    facts = runner.run({
        "config": config, "traffic": traffic, "chips": cell["chips"],
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "peaks": device["peaks"], "t_process": T_PROCESS})

    wanted = manifest["per_layer"] if args.trace else manifest["end_to_end"]
    metrics = {}
    for m in wanted:
        if not applies(m, args.workload):
            continue
        value = read_metric(m["name"], facts)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    notes = dict(facts.get("notes", {}))
    if not args.trace:
        # the per-layer metrics that need no trace ride along under "notes" (not
        # judged): their run-to-run spread is known before one is promoted
        notes["per_layer_untraced"] = {
            m["name"]: read_metric(m["name"], facts)
            for m in manifest["per_layer"]
            if applies(m, args.workload) and m["source"] != "device_trace"}
    line = {"correct": bool(facts["correct"]),
            "attempted": int(facts["attempted"]),
            "failed": int(facts["failed"]), "metrics": metrics,
            "device": {"platform": device["platform"], "kind": device["kind"],
                       "count": device["count"],
                       "memory_peak_bytes": int(facts["memory_peak_bytes"])},
            "notes": notes}
    trace = facts.get("trace")
    if trace:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
