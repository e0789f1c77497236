"""Wall seconds of NodeHost.start_clusters, summed over the NodeHosts of
the deployment (the program's bring-up account: prepare, bootstrap save
and launch of every replica, timed once a host). None on a program that
keeps no such account."""


def read(run):
    return run.client.get("setup.start_clusters_s")
