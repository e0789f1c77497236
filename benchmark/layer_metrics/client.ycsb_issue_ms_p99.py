"""p99 of the time between an operation's completion (stamped on the
completing thread) and the submission of its client's next one by the
one generator thread: `poll_ms` and the thread's wait for the GIL."""


def read(run):
    return run.client.get("client.ycsb_issue_ms_p99")
