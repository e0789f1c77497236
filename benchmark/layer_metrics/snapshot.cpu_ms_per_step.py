"""CPU seconds per launch that the snapshot workers spent saving,
recovering and compacting (`snap.save.cpu` + `snap.recover.cpu` +
`snap.compact.cpu`, summed over the workers): they run beside the loop,
so this is load on the shared GIL. None on a program without the spans
(`snap.save.cpu` stands for them: a window without a save has none)."""


def read(run):
    w = run.window
    phases = w["phases"]
    if w["phase_ratio"] != 1 or "snap.save.cpu" not in phases:
        return None
    if not w["launches"]:
        return None
    total = sum(
        phases.get(name, 0.0)
        for name in ("snap.save.cpu", "snap.recover.cpu", "snap.compact.cpu")
    )
    return total / w["launches"] * 1000.0
