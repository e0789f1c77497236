"""The loop thread's own CPU time per launch in the `save` phase. What
storage.save_ms_per_step has beyond it is the fsync barrier and the
wait for the GIL: Python if the two are close, the disk if not."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "save.cpu")
