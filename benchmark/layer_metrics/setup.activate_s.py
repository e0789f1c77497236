"""Wall seconds the engine spent activating lanes during bring-up (the
host half of every lane's activation and the batched scatter's
dispatch, summed over the batches), from the program's bring-up
account. None on a program that keeps no such account."""


def read(run):
    return run.client.get("setup.activate_s")
