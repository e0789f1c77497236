"""How late the generator issued operations against its own schedule, 99th
percentile over the window, from the generator. A starved generator is
not a fast server: read every latency beside this."""


def read(run):
    return run.client.get("client.late_p99_ms")
