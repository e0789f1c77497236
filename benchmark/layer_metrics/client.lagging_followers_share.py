"""Share of all replicas that were more than one batch behind their
leader's applied count at the window's close (one batch behind is a
follower that lost the last Replicate). Expect 0: the kernel's reject
and resend keeps a follower inside the device window."""


def read(run):
    return run.client.get("client.lagging_followers_share")
