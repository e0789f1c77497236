"""Kernel launches from the first lane activated to the first launch
after which every active lane knows a leader, from the program's
bring-up account. None on a program that keeps no such account."""


def read(run):
    return run.client.get("setup.elect_launches")
