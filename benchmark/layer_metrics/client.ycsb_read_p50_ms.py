"""Median time from submitting one read (read_index) to the record in
hand (read_local_node in the completion callback), in the closed loop of
YCSB clients: the loop's own queue, not a service time."""


def read(run):
    return run.client.get("client.ycsb_read_p50_ms")
