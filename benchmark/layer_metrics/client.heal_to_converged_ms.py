"""ms from the moment the loss stops (the chaos hook is cleared, after
the window's last batches are accounted for) until every replica of
every group holds its group's last acknowledged row. None where the
fleet had not converged within the traffic file's `heal_bound_s`."""


def read(run):
    return run.client.get("client.heal_to_converged_ms")
