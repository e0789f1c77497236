"""Messages the chaos hook dropped over the messages it saw addressed to
a replica that is not its group's leader, inside the window: the loss
really offered (the traffic file asks for 0.10)."""


def read(run):
    return run.client.get("client.dropped_share")
