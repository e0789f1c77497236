"""Leader lanes that staged a row of their clients' proposals or a
ReadIndex context into a launch (`n.lanes_packed`, VectorEngine._pack),
per launch: how many of the deployment's 1 024 groups a step of skewed
single operations really carries work for. Rows of the protocol's own
(a follower's Replicate, an acknowledgement, a heartbeat) do not count:
every lane stages one of those in every launch."""

from benchmark.lib import counters


def read(run):
    return counters.per_pack(run, "lanes_packed")
