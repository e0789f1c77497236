"""Replicates that a follower refused per launch: the kernel's
`ctr_replicate_rejects` (inbox/follower_append: the message's previous
index is past the follower's log or its term differs), summed over the
lanes by `counter_stats()['replicate_rejects']`, which the cell's
generator reads at both ends of the window. Every lost Replicate is
found by the next message's reject, so this is the loss as the protocol
saw it. None in a cell whose generator does not read the counter."""


def read(run):
    n = run.client.get("program_in_window", {}).get("replicate_rejects")
    launches = run.window["launches"]
    return n / launches if n is not None and launches else None
