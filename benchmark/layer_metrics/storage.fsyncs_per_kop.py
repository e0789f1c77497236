"""fsync barriers per 1 000 acknowledged writes over the window, from the
fsync_latency_seconds histogram's count. It guards the durability
guarantee: it may fall by batching inside a step, never by skipping a
flush."""


def read(run):
    acked = run.client.get("writes_acked")
    return run.window["fsyncs"] / (acked / 1000.0) if acked else None
