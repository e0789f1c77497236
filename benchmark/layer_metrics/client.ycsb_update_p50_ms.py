"""Median time from submitting one update to its acknowledgement with
its Result, in the closed loop of YCSB clients: the loop's own queue
(Little's law), not a service time."""


def read(run):
    return run.client.get("client.ycsb_update_p50_ms")
