"""Host-clock time per launch in the engine loop's own phases other than
the launch, the wait for the device and the WAL save: pack, place,
send_rep, send_resp, apply, reads, maintain. From the stage profiler at
full sampling, as a delta over the window."""

PHASES = ("pack", "place", "send_rep", "send_resp", "apply", "reads",
          "maintain")


def read(run):
    w = run.window
    if w["phase_ratio"] != 1 or not w["launches"]:
        return None
    total = sum(w["phases"].get(p, 0.0) for p in PHASES)
    return total / w["launches"] * 1000.0
