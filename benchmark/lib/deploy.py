"""Build a configuration's deployment through NodeHost and bring it up.

Every replica of every NodeHost of a configuration is co-hosted on one
shared engine core (EngineConfig.share_scope), which is the layout all
configurations of the first part state. The configuration file's
`nodehost`, `engine` and `raft` objects are passed through as options.
"""
from __future__ import annotations

import os
import time

from dragonboat_tpu.config import Config, EngineConfig, NodeHostConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.transport.loopback import _Registry, loopback_factory

_SCOPE = "benchmark"


class BringUpFailure(RuntimeError):
    pass


class Cluster:
    """`groups` Raft groups x `replicas` replicas on `replicas` NodeHosts
    sharing one engine core. Groups are numbered from 0 here; their
    cluster ids are group + 1."""

    def __init__(self, config: dict, groups: int, sm_factory, workdir: str,
                 engine_overrides: dict) -> None:
        self.groups = groups
        self.replicas = int(config["deployment"]["replicas"])
        self.steps_per_sync = int(config["engine"].get("steps_per_sync", 1))
        self._raft = dict(config["raft"])
        self._sm_factory = sm_factory
        self._members = {
            n: f"bench:{n}" for n in range(1, self.replicas + 1)
        }
        self._sessions: dict = {}
        engine = dict(config["engine"])
        engine.update(engine_overrides)
        reg = _Registry()
        self.hosts: dict = {}
        for nid, addr in self._members.items():
            self.hosts[nid] = NodeHost(NodeHostConfig(
                raft_address=addr,
                nodehost_dir=os.path.join(workdir, f"nh{nid}"),
                raft_rpc_factory=lambda a: loopback_factory(a, reg),
                engine=EngineConfig(
                    kind="vector",
                    max_groups=self.replicas * groups,
                    share_scope=_SCOPE,
                    **engine,
                ),
                **config["nodehost"],
            ))
        self.core = self.hosts[1].engine.core
        if any(nh.engine.core is not self.core for nh in self.hosts.values()):
            raise BringUpFailure("the NodeHosts do not share one engine core")

    def start(self) -> None:
        for nid, nh in self.hosts.items():
            nh.start_clusters([
                (
                    dict(self._members), False, self._sm_factory,
                    Config(node_id=nid, cluster_id=g + 1, **self._raft),
                )
                for g in range(self.groups)
            ])

    def leaders(self) -> list:
        """Leader's node id per group, 0 where none is known: one pass
        over the engine's host mirrors, no device access."""
        snap = self.hosts[1].engine.leader_snapshot()
        return [snap.get(g + 1, (0, 0))[0] for g in range(self.groups)]

    def wait_leaders(self, bound_s: float) -> list:
        deadline = time.monotonic() + bound_s
        while True:
            leaders = self.leaders()
            if all(leaders):
                return leaders
            if time.monotonic() >= deadline:
                raise BringUpFailure(
                    f"{leaders.count(0)} of {self.groups} groups elected no "
                    f"leader within {bound_s:.0f}s"
                )
            time.sleep(0.05)

    def session(self, nid: int, g: int):
        key = (nid, g)
        s = self._sessions.get(key)
        if s is None:
            s = self._sessions[key] = self.hosts[nid].get_noop_session(g + 1)
        return s

    def stop(self) -> None:
        for nh in self.hosts.values():
            nh.stop()
