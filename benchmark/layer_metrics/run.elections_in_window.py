"""Campaigns started inside the window, counted on the device
(counter_stats()['elections_started'] delta). Expect 0 without faults."""


def read(run):
    return run.window["elections_started"]
