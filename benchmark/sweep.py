"""Find an open-loop cell's knee, once, on the chip.

    python3 benchmark/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 2000,4000,8000,...

One deployment, brought up as run.py does; then the cell's own generator
at each rate in turn, lowest first, `--seconds` each. A row per rate:
completed operations per second, write and read latency, failures, and
how late the generator ran. The sweep stops at the first
rate the system does not sustain (the writes due in the window's second
half take over 1.5 times as long as those of its first half, so the
queue is growing, or the median passes two seconds): beyond the knee the
backlog of one point would spoil the next. The cell's traffic file then carries
0.8 x the highest sustained rate as a number; a run never searches.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated operations per second")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    _spec, cell, config, traffic = run.load_cell(args.workload)

    from benchmark.lib import deploy, loadgen

    groups, scale = run.sizes(config, args.rehearsal)
    ledger = loadgen.Ledger(loadgen.Payloads(args.seed, groups), groups)
    kind = run.load_plugin("generators", traffic["kind"])
    sm_factory = run.load_plugin(
        "statemachines", config["statemachine"]
    ).StateMachine
    _devs, device = run.open_backend(args.rehearsal, int(cell["chips"]))
    workdir = run.fresh_workdir(args.workload + ".sweep")
    cluster = deploy.Cluster(config, groups, sm_factory, workdir, {})
    try:
        cluster.start()
        cluster.wait_leaders(run.ELECT_S)
        for n, rate in enumerate(float(r) for r in args.rates.split(",")):
            gen = kind.Generator(
                dict(traffic, rate_ops_per_s=rate), groups, ledger,
                args.seed + n, args.seconds, scale,
            )
            if n == 0:
                gen.warm(cluster)
            gen.measure(cluster, lambda t: None, lambda t: None)
            row = gen.results()
            row["rate_ops_per_s"] = rate * scale
            print("[sweep] " + json.dumps(row), flush=True)
            if (row["commit_latency_p50_ms_second_half"]
                    > 1.5 * row["commit_latency_p50_ms_first_half"]
                    or row["commit_latency_p50_ms"] > 2000.0):
                break
        steps = cluster.core.step_stats()
        print("[sweep] " + json.dumps(
            {"device": device, "loop_exceptions": steps["loop_exceptions"]}
        ))
    finally:
        cluster.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    if args.rehearsal:
        print("REHEARSAL on cpu at a tiny size: not a result")
    return 0


if __name__ == "__main__":
    sys.exit(main())
