"""Groups that finished no whole submit-to-accounted cycle inside the
window: they enter committed_ops_per_s by what they acknowledged inside
the window over the window's length. Expect 0."""


def read(run):
    return run.client.get("client.stalled_groups")
