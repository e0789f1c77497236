"""Configuration for Raft groups and NodeHost instances.

Mirrors the three-tier config system of the reference (cf. config/config.go:60-169
for the per-group Config, config/config.go:211-307 for NodeHostConfig) with the
same validation rules, plus TPU-engine specific knobs (EngineConfig) that have
no referent in the Go implementation.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

from .types import CompressionType


class ConfigError(ValueError):
    pass


@dataclass
class Config:
    """Per-Raft-group configuration (cf. config/config.go:60-169)."""

    node_id: int = 0
    cluster_id: int = 0
    check_quorum: bool = False
    election_rtt: int = 0
    heartbeat_rtt: int = 0
    snapshot_entries: int = 0
    compaction_overhead: int = 0
    ordered_config_change: bool = False
    max_in_mem_log_size: int = 0
    snapshot_compression_type: CompressionType = CompressionType.NO_COMPRESSION
    entry_compression_type: CompressionType = CompressionType.NO_COMPRESSION
    is_observer: bool = False
    is_witness: bool = False
    quiesce: bool = False
    # Pre-vote (Raft thesis 9.6): before a real campaign the replica runs
    # a non-disruptive poll at term+1 — the prospective candidate's term
    # and the voters' terms/votes stay untouched until a quorum confirms
    # the election could be won. Stops a rejoining/partition-healed
    # replica from bumping a stable quorum's term. Off by default: the
    # False path is bit-identical to the pre-knob protocol.
    pre_vote: bool = False
    # Leader leases: a leader that heard heartbeat acks from a quorum
    # within one heartbeat round serves linearizable reads LOCALLY (no
    # ReadIndex quorum round-trip) until the lease expires. The lease is
    # bounded strictly below the minimum randomized election timeout
    # minus the skew margin (lease_margin_rtt), so no rival can win an
    # election while a live lease could still serve reads — provided
    # host clocks drift less than the margin per election window; the
    # ClockPlane chaos apparatus (faults.py) attacks exactly that
    # assumption and the watchdog-detected clock-anomaly path revokes
    # the lease rather than trusting it. Off by default: the False path
    # is bit-identical to the pre-knob protocol, and an expired/revoked
    # lease always falls back to the ReadIndex path (degradation, not
    # danger).
    lease_read: bool = False
    # Skew margin in RTT ticks subtracted from the lease lifetime:
    # lease duration = election_rtt - lease_margin_rtt, granted from
    # the quorum round's START tick. 0 = auto (one heartbeat_rtt).
    # Must leave a positive lease: lease_margin_rtt < election_rtt -
    # heartbeat_rtt (the grant lags the round start by up to one
    # heartbeat round-trip).
    lease_margin_rtt: int = 0

    def validate(self) -> None:
        # cf. config/config.go:176-208 Validate
        if self.node_id == 0:
            raise ConfigError("invalid NodeID, it must be >= 1")
        if self.heartbeat_rtt == 0:
            raise ConfigError("HeartbeatRTT must be > 0")
        if self.election_rtt == 0:
            raise ConfigError("ElectionRTT must be > 0")
        if self.election_rtt <= 2 * self.heartbeat_rtt:
            raise ConfigError(
                "invalid election rtt, ElectionRTT must be > 2 * HeartbeatRTT"
            )
        if self.max_in_mem_log_size > 0 and self.max_in_mem_log_size < 64:
            raise ConfigError("MaxInMemLogSize is too small")
        if self.is_witness and self.snapshot_entries > 0:
            raise ConfigError("witness node can not take snapshot")
        if self.is_witness and self.is_observer:
            raise ConfigError("witness node can not be an observer")
        if self.lease_margin_rtt < 0:
            raise ConfigError("LeaseMarginRTT must be >= 0")
        if self.lease_read:
            if self.is_witness or self.is_observer:
                raise ConfigError(
                    "witness/observer node can not serve lease reads"
                )
            margin = self.lease_margin_rtt or self.heartbeat_rtt
            if margin >= self.election_rtt - self.heartbeat_rtt:
                raise ConfigError(
                    "invalid lease margin, LeaseMarginRTT must be < "
                    "ElectionRTT - HeartbeatRTT or the lease never opens"
                )

    def lease_margin_ticks(self) -> int:
        """The effective skew margin (ticks) a lease grant subtracts:
        the configured LeaseMarginRTT, or one heartbeat RTT when auto."""
        return self.lease_margin_rtt or self.heartbeat_rtt

    def get_max_in_mem_log_size(self) -> int:
        if self.max_in_mem_log_size == 0:
            return 2**63 - 1
        return self.max_in_mem_log_size


@dataclass
class EngineConfig:
    """TPU batched-engine knobs; no referent in the reference implementation.

    The vectorized engine advances all groups in a fixed-capacity tensor
    program; these values bound the static shapes of that program. Larger
    values raise per-step HBM footprint but amortize kernel-launch overhead
    over more protocol work.
    """

    # "vector" = the device-kernel engine (engine/vector.py) advancing all
    # groups in one compiled step — the TPU-native flagship and the
    # default; "scalar" = per-group Python Peer stepping
    # (engine/execengine.py), kept as the portable fallback/oracle.
    kind: str = "vector"
    # Shard the engine's (G, ...) state over every visible jax device
    # (jax.sharding.Mesh along the group axis). Groups are independent
    # Raft instances, so at steps_per_sync=1 the kernel partitions with
    # no cross-device collectives on the hot path. Composed with
    # steps_per_sync>1 the inter-step router all-gathers candidate
    # messages across shards inside the launch so co-hosted replicas on
    # different chips still talk without the host. Needs more than one
    # visible device (raises otherwise). max_groups is rounded up to a
    # device multiple; the round-up is stamped in step_stats
    # (padded_groups/mesh_devices) and ghost lanes are never allocated.
    shard_over_mesh: bool = False
    # Max Raft groups per NodeHost; the G dimension of the kernel tensors.
    # (Default sized for fast bring-up; large fleets raise it explicitly.)
    max_groups: int = 128
    # Max peers per group (incl. self); the P dimension.
    max_peers: int = 8
    # Device-resident log window per group (entries of (term) metadata).
    log_window: int = 256
    # Max inbound protocol messages consumed per group per kernel step.
    inbox_depth: int = 8
    # Max outstanding ReadIndex system contexts per group on device.
    readindex_depth: int = 4
    # Max entries carried by one inbox row / Replicate message. The kernel's
    # ring-slot scatter is O(G*W) regardless of this value, so raising it
    # widens per-step ingestion at the cost of inbox transfer size only.
    max_entries_per_msg: int = 8
    # Protocol steps per kernel launch (K). At K=1 the engine runs the
    # one-step loop: every message between replicas becomes a host Message
    # and rides the next launch. At K>1 the step body runs under a
    # lax.scan and co-hosted replica traffic (Replicate/acks/heartbeats/
    # votes between lanes of one shared core) is routed ON DEVICE between
    # inner steps, while host-only work (WAL save, SM apply, client
    # notify, cross-host sends) accumulates in per-step output slots and
    # drains once a launch: one dispatch, one fetch and ONE merged save
    # wave, fsyncs included, before anything of that launch is sent as a
    # response, applied or notified.
    # None = auto: the engine holds both programs and chooses at every
    # launch boundary from what its route table says. K=3 (a commit is
    # leader append, follower append and acknowledge, leader commit:
    # three protocol steps, PERF.md section 5) while every peer slot of
    # every active lane is routable on the device: co-hosted on this
    # core, not a witness, its host not partitioned, no lane under
    # restore, no chaos hook. K=1 otherwise. A switch down first drains
    # what the last K=3 launch left parked on the device. The K=3
    # program is built when the first routable peer appears (an engine
    # without one never builds it) and compiled by its first launch,
    # during bring-up. With shard_over_mesh, None stays at K=1.
    # An integer forces exactly that K for the engine's life (it is the
    # scan length compiled into the program); with shard_over_mesh the
    # sharded K-step kernel routes cross-shard lane traffic device-to-
    # device and stays bit-identical to the unsharded reference.
    steps_per_sync: "Optional[int]" = None
    # Hide the kernel behind host work (K=1): dispatch kernel step t, run
    # step t-1's maintenance (window compaction, snapshot triggers,
    # catch-up of parked peers) while the device computes, and fetch and
    # decode step t at the top of the next iteration, before the next
    # pack. Everything of a step that sends a message or acknowledges a
    # request is decoded before the next launch, so a Raft hop takes one
    # launch with the option on as with it off; only maintenance, which
    # no request waits for, is deferred, and _pack sees the device
    # window's first index one maintenance late (it throttles on it, so
    # late only means a little less room). On the cpu backend the "wait"
    # is the host computing the kernel, so there is nothing to hide.
    # None = auto: on for accelerators, off for cpu. False on the chip
    # shows the kernel on every step (PERF.md, PR 25). Ignored by every
    # launch of more than one step, whether steps_per_sync set it or the
    # engine chose it: the next pack needs that launch's fetch.
    overlap_decode: "Optional[bool]" = None
    # Stage-profiler sampling for the vector engine hot loop: 0 = sparse
    # default (1 in 32 iterations — steady-state cost is two clock reads
    # per stage only on sampled iterations), 1 = record every step (full
    # stage timings; the benchmark's traced run and debugging), N>1 =
    # sample 1/N.
    profile_sample_ratio: int = 0
    # Per-step cap on coalesced tick backlogs after an engine loop stall
    # (cold compile, CPU contention between co-scheduled loops). Backlog
    # beyond the cap is SHED, not deferred: a stall compresses into at
    # most this many logical ticks per step and the rest of the wall-
    # clock time is simply not charged to timers — which is what keeps
    # the randomized election-timer spread intact (the old election-RTT
    # cap charged a whole election timeout in one step, synchronizing
    # every follower's timeout into split-vote storms). Tick-denominated
    # timeouts therefore stretch across stalls by design. 0 = auto: each
    # lane's heartbeat RTT; never exceeds a lane's election RTT.
    max_catchup_ticks: int = 0
    # Tick-fairness watchdog yield threshold in milliseconds: an engine
    # loop iteration longer than this yields the CPU to co-scheduled peer
    # loops it starved (see engine/fairness.py). None = auto
    # (max(4 tick periods, 20ms)); 0 disables enforcement (the starvation
    # gauge keeps measuring either way).
    fairness_yield_ms: "Optional[float]" = None
    # Co-hosted engine sharing: NodeHosts in one process constructed with
    # the same non-None scope string share ONE VectorEngine device state, so
    # all their replicas advance in a single kernel step and messages
    # between them short-circuit the transport (the TPU-native deployment
    # shape: one engine per accelerator host, many NodeHost replicas on it).
    share_scope: "Optional[str]" = None


@dataclass
class NodeHostConfig:
    """Per-process configuration (cf. config/config.go:211-307)."""

    deployment_id: int = 0
    wal_dir: str = ""
    nodehost_dir: str = ""
    rtt_millisecond: int = 0
    raft_address: str = ""
    listen_address: str = ""
    mutual_tls: bool = False
    ca_file: str = ""
    cert_file: str = ""
    key_file: str = ""
    max_send_queue_size: int = 0
    max_receive_queue_size: int = 0
    logdb_factory: Optional[Callable] = None
    raft_rpc_factory: Optional[Callable] = None
    enable_metrics: bool = False
    raft_event_listener: Optional[object] = None
    system_event_listener: Optional[object] = None
    max_snapshot_send_bytes_per_second: int = 0
    max_snapshot_recv_bytes_per_second: int = 0
    # outbound snapshot stream caps (cf. lane.go:40-237 + StreamConnections
    # config.go:299-306): total concurrent lanes and per-target lanes; a
    # request over either cap fails fast through the snapshot-status
    # feedback path instead of queuing an unbounded thread
    max_snapshot_connections: int = 8
    max_snapshot_lanes_per_target: int = 2
    engine: EngineConfig = field(default_factory=EngineConfig)

    def validate(self) -> None:
        # cf. config/config.go:309-345 Validate
        if self.rtt_millisecond == 0:
            raise ConfigError("invalid RTTMillisecond")
        if not _is_valid_address(self.raft_address):
            raise ConfigError("invalid NodeHost address")
        if self.listen_address and not _is_valid_address(self.listen_address):
            raise ConfigError("invalid ListenAddress")
        if self.mutual_tls:
            if not self.ca_file:
                raise ConfigError("CA file not specified")
            if not self.cert_file:
                raise ConfigError("cert file not specified")
            if not self.key_file:
                raise ConfigError("key file not specified")
        if 0 < self.max_send_queue_size < 64:
            raise ConfigError("MaxSendQueueSize value is too small")
        if 0 < self.max_receive_queue_size < 64:
            raise ConfigError("MaxReceiveQueueSize value is too small")

    def get_listen_address(self) -> str:
        return self.listen_address or self.raft_address


def _is_valid_address(addr: str) -> bool:
    if not addr or ":" not in addr:
        return False
    host, _, port = addr.rpartition(":")
    if not host:
        return False
    try:
        p = int(port)
    except ValueError:
        return False
    return 0 < p < 65536
