"""Run one cell of the benchmark once, in this one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by the names
BENCHMARK.json gives them; nothing in this file names one. The run builds
the deployment through NodeHost, elects, warms the cell's own shapes,
measures for --seconds, reads back every group against the plain
reference and prints one JSON line last. With --trace 0 the line carries
the cell's end-to-end metrics, taken by the generator on the host clock
with the engine's instrumentation at its defaults; with --trace 1 it
carries the per-layer metrics, taken with full stage sampling and the
device profiler over part of the window.

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result. --rehearsal runs the same code on the CPU
at a tiny size, says REHEARSAL, and is never a result.
"""
from __future__ import annotations

import time

_T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

REHEARSAL_GROUPS = 4
ELECT_S = 300.0
HARD_LIMIT_S = 1150  # thread dump and exit 1 before the driver's limit


def load_plugin(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module: one file per generator,
    state machine and per-layer metric, found by name."""
    path = os.path.join(_HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def metrics_of(spec: dict, section: str, workload: str) -> list:
    return [
        m for m in spec[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def load_cell(workload: str):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic)."""
    spec = load_json(_ROOT, "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(_ROOT, entry["file"])
    traffic = load_json(_HERE, "traffic", cell["traffic"] + ".json")
    return spec, cell, config, traffic


def sizes(config: dict, rehearsal: bool):
    """(groups, scale): a rehearsal runs REHEARSAL_GROUPS groups and
    offers that share of an open loop's rate."""
    groups = int(config["deployment"]["groups"])
    if rehearsal:
        return REHEARSAL_GROUPS, REHEARSAL_GROUPS / groups
    return groups, 1.0


def open_backend(rehearsal: bool, chips: int):
    """Start JAX's backend with the compile cache on and return (devices,
    the device as JAX reports it). Exits where the cell's chips are not
    there: there is no CPU fallback."""
    import jax

    from dragonboat_tpu._jaxenv import enable_compile_cache, pin_cpu

    if rehearsal:
        pin_cpu(n_devices=chips if chips > 1 else None)
    enable_compile_cache()
    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if not rehearsal and (device["platform"] != "tpu" or len(devs) < chips):
        sys.exit(
            f"benchmark: found {len(devs)} x {device['platform']}, the cell "
            f"needs {chips} TPU chip(s); there is no CPU fallback "
            "(--rehearsal is a tiny CPU rehearsal, never a result)"
        )
    return devs, device


def fresh_workdir(workload: str) -> str:
    """WAL directories live inside the checkout, removed at both ends."""
    workdir = os.path.join(_HERE, ".work", workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    return workdir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU rehearsal of the same code; never a result")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True)
    spec, cell, config, traffic = load_cell(args.workload)

    # the inputs are made from the seed before the backend starts
    from benchmark.lib import check, deploy, loadgen, probes, tracing

    groups, scale = sizes(config, args.rehearsal)
    ledger = loadgen.Ledger(loadgen.Payloads(args.seed, groups), groups)
    gen = load_plugin("generators", traffic["kind"]).Generator(
        traffic, groups, ledger, args.seed, args.seconds, scale
    )
    sm_factory = load_plugin("statemachines", config["statemachine"]).StateMachine
    marks = {"made_inputs": time.monotonic()}
    devs, device = open_backend(args.rehearsal, int(cell["chips"]))
    marks["backend"] = time.monotonic()

    workdir = fresh_workdir(args.workload)
    overrides = {"profile_sample_ratio": 1} if args.trace else {}
    cluster = deploy.Cluster(config, groups, sm_factory, workdir, overrides)
    window: dict = {}
    tracer = None
    try:
        sharded = len(cluster.core._state.term.sharding.device_set)
        if bool(config["engine"].get("shard_over_mesh")) != (sharded > 1):
            raise deploy.BringUpFailure(
                f"engine state is on {sharded} device(s), the configuration "
                f"says shard_over_mesh={config['engine'].get('shard_over_mesh')}"
            )
        marks["built"] = time.monotonic()
        cluster.start()
        marks["started"] = time.monotonic()
        cluster.wait_leaders(ELECT_S)
        marks["elected"] = time.monotonic()
        gen.warm(cluster)
        marks["warmed"] = time.monotonic()
        if args.trace:
            tracer = tracing.WindowTrace(
                cluster, os.path.join(workdir, "trace"), args.seconds
            )

        def on_open(t_open: float) -> None:
            window["open"] = probes.snapshot(cluster, t_open)
            if tracer is not None:
                tracer.start(t_open)

        def on_close(t_close: float) -> None:
            window["close"] = probes.snapshot(cluster, t_close)

        gen.measure(cluster, on_open, on_close)
        marks["measured"] = time.monotonic()
        client = gen.results()
        delta = probes.delta(
            window["open"], window["close"], cluster.steps_per_sync
        )
        trace = breakdown = None
        if tracer is not None:
            trace, breakdown = tracer.reduce(cluster.steps_per_sync)
        marks["reduced"] = time.monotonic()
        correct = client["reads_wrong"] == 0
        try:
            checked = check.read_back(cluster, ledger, args.seed)
        except loadgen.CheckFailure as e:
            print(f"[check] FAILED: {e}", flush=True)
            correct, checked = False, {}
        marks["checked"] = time.monotonic()
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs
        ]
        kcfg = cluster.core.kcfg
    finally:
        cluster.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    setup_s = window["open"]["t"] - _T_PROCESS
    device["memory_peak_bytes"] = max(peaks)
    metrics = {}
    if args.trace:
        all_peaks = load_json(_HERE, "lib", "peaks.json")
        if not args.rehearsal and device["kind"] not in all_peaks:
            sys.exit(f"benchmark: no peaks for device kind {device['kind']!r}")
        run = types.SimpleNamespace(
            client=client, window=delta, trace=trace,
            peaks=all_peaks.get(device["kind"]),
            memory_peak_bytes=device["memory_peak_bytes"],
            shapes={
                "G": kcfg.groups, "P": kcfg.peers, "W": kcfg.log_window,
                "K": kcfg.inbox_depth, "E": kcfg.max_entries_per_msg,
                "R": kcfg.readindex_depth,
                "steps_per_sync": cluster.steps_per_sync, "shards": sharded,
            },
        )
        for m in metrics_of(spec, "per_layer", args.workload):
            value = load_plugin("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
    else:
        client["setup_s"] = setup_s
        for m in metrics_of(spec, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": client[m["name"]], "unit": m["unit"]}

    split, prev = {}, _T_PROCESS
    for name, t in marks.items():  # in the order they were stamped
        split[name] = round(t - prev, 3)
        prev = t
    line = {
        "correct": bool(correct),
        "attempted": client["attempted"],
        "failed": client["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    # for people: everything measured, whichever --trace
    print("[run] " + json.dumps({
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "setup_s": setup_s, "phase_seconds": split, "client": client,
        "window": delta, "trace_window": trace, "checked": checked,
    }, default=str), flush=True)
    faulthandler.cancel_dump_traceback_later()
    if args.rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line))
    if args.rehearsal:
        print("REHEARSAL on cpu at a tiny size: not a result")
    return 0


if __name__ == "__main__":
    sys.exit(main())
