"""Dev harness: run the e2e bench regime and dump per-host engine stage
profiles (not part of the driver bench; see bench.py for the headline)."""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time

from bench import bench_e2e


def main() -> None:
    groups = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    duration = float(sys.argv[2]) if len(sys.argv) > 2 else 6.0
    wave = int(sys.argv[3]) if len(sys.argv) > 3 else 128
    import bench as benchmod
    import dragonboat_tpu.nodehost as nodehost_mod

    profiles = {}
    orig_stop = nodehost_mod.NodeHost.stop

    def stop_with_profile(self):
        eng = getattr(self, "engine", None)
        if eng is not None and hasattr(eng, "profile_summary"):
            profiles[self.config.raft_address] = eng.profile_summary()
        return orig_stop(self)

    nodehost_mod.NodeHost.stop = stop_with_profile
    workdir = tempfile.mkdtemp(prefix="dbtpu-prof-")
    try:
        r = bench_e2e(groups, duration, 16, workdir, wave=wave)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(r, indent=1))
    for addr, sm in profiles.items():
        print(f"--- {addr}")
        for name, d in sorted(sm.items(), key=lambda kv: -kv[1]["total_s"]):
            print(
                f"  {name:10s} n={int(d['n']):7d} mean={d['mean_s']*1e6:9.1f}us"
                f" p99={d['p99_s']*1e6:9.1f}us total={d['total_s']:7.2f}s"
            )


if __name__ == "__main__":
    main()
