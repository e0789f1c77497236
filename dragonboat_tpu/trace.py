"""Sampled latency profiler for the execution engine hot loop, request
latency plumbing, and the flight recorder.

cf. reference trace.go:29-162: bounded percentile samples (p50/p99/p999)
per pipeline stage, recorded every `sample_ratio` iterations so the
steady-state cost is one time.monotonic() pair per stage only on sampled
iterations, nothing otherwise. Dumped via logger at engine stop
(cf. execengine.go:197-211).

This module also hosts the observability plane's two cheap primitives:

  * LatencySampler / LatencyTrace — the sampled-request seam: 1-in-N
    requests get a trace object stamped at propose/commit/apply; the rest
    pay one integer increment and stay allocation-free.
  * FlightRecorder — a bounded, lock-free (GIL-atomic deque) ring of
    structured events with monotonic timestamps. Subsystems append
    postmortem-grade breadcrumbs (leader changes, breaker transitions,
    queue evictions, fault injections, fairness clamps); the pytest
    failure hook dumps the ring as JSONL next to the CHAOS_SEED so chaos
    replays come with a timeline.
"""
from __future__ import annotations

import itertools
import json
import mmap
import os
import random
import struct
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional, Tuple


class Sample:
    """Bounded reservoir sample with cheap percentiles (cf. trace.go:29-96).

    Reservoir semantics (Vitter's algorithm R, deterministic per-name
    seed): every recorded value has equal probability of being in the
    reservoir, so long-run percentiles reflect the WHOLE run. The old
    fill-then-freeze cap silently dropped everything after the first 50k
    values, skewing percentiles toward bring-up. mean() stays exact (sum
    over all values); __len__ reports values SEEN."""

    __slots__ = ("name", "_vals", "_cap", "_seen", "_sum", "_rng")

    def __init__(self, name: str, cap: int = 50_000) -> None:
        self.name = name
        self._vals: List[float] = []
        self._cap = cap
        self._seen = 0
        self._sum = 0.0
        # deterministic seed: same name + same value stream => same
        # reservoir, so profiler output is reproducible run to run
        self._rng = random.Random(zlib.crc32(name.encode()) + cap)

    def record(self, v: float) -> None:
        self._seen += 1
        self._sum += v
        if len(self._vals) < self._cap:
            self._vals.append(v)
        elif self._cap:  # cap 0: count and sum alone, no percentiles
            j = self._rng.randrange(self._seen)
            if j < self._cap:
                self._vals[j] = v

    def __len__(self) -> int:
        return self._seen

    def percentile(self, p: float) -> float:
        if not self._vals:
            return 0.0
        s = sorted(self._vals)
        k = min(len(s) - 1, max(0, int(p * len(s))))
        return s[k]

    def mean(self) -> float:
        return self._sum / self._seen if self._seen else 0.0


class Profiler:
    """Sampled stage profiler of one engine loop (cf. trace.go:98-162).
    Stage names are open-ended: samples are created on first use.

    Two ways to time a stage on the owning loop thread, both free on
    unsampled iterations:

      * start()/end(stage) — a pair around one stage (the scalar engine's
        workers); what runs between an end() and the next start() is
        not timed;
      * begin(stage) — opens `stage` and closes the span that was
        running, so consecutive spans are contiguous: from the first
        begin() of a sampled iteration to the next new_iteration() every
        instant lies in exactly one of them (the vector engine's loop).
        Each also records the thread's CPU seconds under `<stage>.cpu`.

    add() records a sub-span the caller timed inside one of those.
    observe() and fold() take no gate and may be called from any thread:
    the apply workers' spans and the request path, whose sampling is the
    request's own. Dotted names (`save.cpu`, `rsm.handle`, `req.w.queue`,
    `n.spans_dropped`) live in `samples` only and are no stages of the
    loop."""

    def __init__(self, sample_ratio: int = 16) -> None:
        self.ratio = max(1, sample_ratio)
        self._iter = 0
        self.sampling = False
        self.samples: Dict[str, Sample] = {}
        self._t0: Optional[float] = None
        # optional histogram sink (profile.PhasePlane): sampled stage
        # durations fan out to engine_phase_seconds; unsampled iterations
        # never reach it
        self._plane = None
        self._engine_kind = ""
        self._sub_kind = ""
        self._span_gate = False
        # begin(): the running span's name, and its start on the wall
        # clock and on this thread's CPU clock
        self._stage: Optional[str] = None
        self._mark = 0.0
        self._cpu_mark = 0.0
        # span events of an iteration's head stages wait until the
        # iteration begins a stage outside the head; an iteration that
        # never does launched nothing, and its head joins the idle
        # stretch that the next one's first head span (`wait`) carries
        self._head: Tuple[str, ...] = ()
        self._held: List[Tuple[str, float, float]] = []
        self._idle_from: Optional[float] = None
        self._shed = 0  # spans the full store shed on this profiler's appends
        self._turn = False  # the next begin() is the first of an iteration

    def attach_phase_plane(
        self, plane, engine_kind: str, idle_head: Tuple[str, ...] = ()
    ) -> None:
        """Tee sampled stage durations into a profile.PhasePlane under
        the given engine kind ("vector"/"exec"), sub-spans under
        `<kind>.sub`. Histograms fill at ANY sampling ratio; the events
        of the kind's own spans, which are disjoint, reach the
        flight recorder's span store only at FULL sampling (ratio 1, the
        benchmark's traced run and debugging). `idle_head` names the
        stages every iteration of a begin() loop starts with before it
        knows whether it has work: an idle loop polling every 2 ms then
        leaves one growing span instead of 1 500 events a second."""
        self._plane = plane
        self._engine_kind = engine_kind
        self._sub_kind = engine_kind + ".sub"
        self._span_gate = self.ratio == 1
        self._head = tuple(idle_head)

    def new_iteration(self) -> None:
        was = self.sampling
        self._iter += 1
        self.sampling = self._iter % self.ratio == 0
        if was and self._stage is not None:
            if self.sampling:
                # the first begin() ends the last span of the iteration
                # before at the instant it opens its own: no gap
                self._turn = True
            else:
                self.close()
                self._end_iteration()

    def _end_iteration(self) -> None:
        """A sampled begin() iteration has closed its last span."""
        if self._span_gate:
            if self._held:  # nothing but head stages: an idle iteration
                if self._idle_from is None:
                    self._idle_from = self._held[0][1]
                self._held.clear()
            self.fold("n.spans_dropped", self._shed)
            self._shed = 0

    def start(self) -> None:
        if self.sampling:
            self._t0 = time.monotonic()

    def end(self, stage: str) -> None:
        if self.sampling and self._t0 is not None:
            now = time.monotonic()
            self.observe(stage, now - self._t0)
            if self._span_gate:
                self._span(self._engine_kind, stage, self._t0, now)
            self._t0 = None

    def begin(self, stage: str) -> None:
        if self.sampling:
            now = time.monotonic()
            cpu = time.thread_time()
            if self._stage is not None:
                self._close(now, cpu)
            if self._turn:
                self._turn = False
                self._end_iteration()
            self._stage, self._mark, self._cpu_mark = stage, now, cpu
            if self._span_gate:
                if self._held and stage not in self._head:
                    for i, (name, t0, t1) in enumerate(self._held):
                        if i == 0 and self._idle_from is not None:
                            t0, self._idle_from = self._idle_from, None
                        self._span(self._engine_kind, name, t0, t1)
                    self._held.clear()
                # the running span shows in dumps with the end it has so
                # far; what is held does not, its stretch may yet merge
                opens = flight_recorder().open_spans
                if self._held:
                    opens.pop(id(self), None)
                else:
                    opens[id(self)] = (
                        self._engine_kind, stage, self._idle_from or now
                    )

    def close(self) -> None:
        """End the span begin() left running (no-op without one)."""
        if self._stage is not None:
            self._close(time.monotonic(), time.thread_time())
            flight_recorder().open_spans.pop(id(self), None)

    def _close(self, now: float, cpu: float) -> None:
        stage, t0 = self._stage, self._mark
        self._stage = None
        self.observe(stage, now - t0, cpu - self._cpu_mark)
        if self._span_gate:
            if stage in self._head:
                self._held.append((stage, t0, now))
            else:
                self._span(self._engine_kind, stage, t0, now)

    def _span(self, engine: str, stage: str, t0: float, t1: float) -> None:
        if flight_recorder().span(engine, stage, t0, t1):
            self._shed += 1

    def add(
        self,
        stage: str,
        dt: float,
        cpu: Optional[float] = None,
        end: Optional[float] = None,
    ) -> None:
        """Record a sub-span the CALLER measured on the loop thread
        (nested inside another stage, e.g. the bulk deliver seam inside
        the send phases, or the device_put inside dispatch): samples and
        a histogram under `<kind>.sub`, with the thread's `cpu` seconds
        where the caller read them. A sub-span that is one stretch of
        time says when it ended (`end`, on time.monotonic()) and at full
        sampling leaves a `phase_span` event under `<kind>.sub`, so a
        dump shows it inside its stage; one that is a sum of pieces
        (save.encode over the shards) has no end and leaves none.
        Sampled iterations only; callers gate their own time.monotonic()
        pair on `self.sampling` so the off path stays clock-read-free."""
        if self.sampling:
            self.observe(stage, dt, cpu, engine=self._sub_kind)
            if end is not None and self._span_gate:
                self._span(self._sub_kind, stage, end - dt, end)

    def observe(
        self,
        stage: str,
        dt: float,
        cpu: Optional[float] = None,
        engine: Optional[str] = None,
    ) -> None:
        """One span of `dt` wall seconds (and `cpu` seconds of the
        calling thread) into the samples and the histogram of `engine`
        (the profiler's own kind if not given). No gate and no event:
        the caller has decided that this span is sampled."""
        s = self.samples.get(stage)
        if s is None:
            s = self.samples.setdefault(stage, Sample(stage))
        s.record(dt)
        if cpu is not None:
            self.fold(stage + ".cpu", cpu)
        if self._plane is not None:
            self._plane.on_phase(engine or self._engine_kind, stage, dt, True)

    def fold(self, name: str, value: float) -> None:
        """Add `value` to the sample `name`, which keeps a count and a
        sum and no percentiles: a counter's increments, a `.cpu`
        companion, one request's share of a stretch. Callable from any
        thread, like observe(). Two threads recording into one name at
        once may lose an increment of its count or sum (a few in a
        million at the rates here, tolerated: telemetry); a reservoir
        cannot tear, because Sample.record only appends or overwrites a
        slot below the length the list already has, and the first use of
        a name creates its Sample once (dict.setdefault is atomic)."""
        s = self.samples.get(name)
        if s is None:
            s = self.samples.setdefault(name, Sample(name, cap=0))
        s.record(value)


# ---------------------------------------------------------------------------
# sampled request latency (the proposal-lifecycle histograms' cheap seam)
# ---------------------------------------------------------------------------


# trace-id minting: a compact u64 that rides the sampled LatencyTrace path
# (1-in-N proposals; the other N-1 never mint, never record). The high 32
# bits are a per-process random salt so merged dumps from N nodes never
# collide; the low 32 bits are a process-local counter. itertools.count is
# a C-level iterator, so minting is one next() + two shifts.
_TRACE_SALT = int.from_bytes(os.urandom(4), "little") or 1
_trace_counter = itertools.count(1)


def mint_trace_id() -> int:
    return (_TRACE_SALT << 32) | (next(_trace_counter) & 0xFFFFFFFF)


class LatencySampler:
    """1-in-N request sampler. sample() costs one increment + one modulo;
    only sampled requests allocate a LatencyTrace, so the unsampled hot
    path stays allocation-free. Counter races under free threading lose or
    add the odd sample — telemetry, not accounting."""

    __slots__ = ("ratio", "_n")

    def __init__(self, ratio: int) -> None:
        self.ratio = max(1, int(ratio))
        self._n = 0

    def sample(self) -> bool:
        self._n += 1
        return self._n % self.ratio == 0


class LatencyTrace:
    """Per-sampled-request timestamps, carried on the proposed Entry (the
    same object travels propose -> arena -> commit -> apply on the
    proposing node, so the engine stamps it without a registry lookup)
    or, for a read, on its RequestState. `owner` pins observation to the
    proposing node — co-hosted replicas apply the identical Entry objects
    and must not double-count; `done` makes observation exactly-once-ish.

    Each stamp is an instant (time.monotonic) and, but for `t_apply0`,
    the engine's launch ordinal at that instant (VectorEngine.launch_no;
    0 on an engine that has none): `t0` enqueue, `t_pack` taken out of the node's queue into
    a launch, `t_commit` quorum commit seen by the host (a read: its
    context confirmed), `t_apply0` an apply worker took up the ready
    nodes, the one holding it among them (writes only), `t_done` applied
    and the waiter notified.

    `trace_id` is the cross-node causal key: minted at propose time
    (mint_trace_id), copied onto the proposed Entry (and from there onto
    wire Messages), and stamped into every flight-recorder event the
    request touches — so merged multi-node dumps reconstruct one
    proposal's propose -> replicate -> quorum -> apply chain. Reads
    carry none."""

    __slots__ = (
        "owner", "trace_id", "done",
        "t0", "t_pack", "t_commit", "t_apply0", "t_done",
        "n0", "n_pack", "n_commit", "n_done",
    )

    def __init__(self, owner, t0: float, trace_id: int = 0, n0: int = 0) -> None:
        self.owner = owner
        self.trace_id = trace_id
        self.done = False
        self.t0 = t0
        self.t_pack = self.t_commit = self.t_apply0 = self.t_done = 0.0
        self.n0 = n0
        self.n_pack = self.n_commit = self.n_done = 0

    def fold(self, prof: Profiler, kind: str) -> None:
        """At t_done, once: this request's share of each stretch of its
        path into the engine's profiler, under `req.<kind>.*` (kind "w":
        queue, replicate, apply_wait, apply; kind "r": queue, confirm,
        complete), with the launches from pack to commit and a count of
        one; the stretches add up to t_done - t0. A request that an
        engine stamped
        only in part (the scalar engine packs nothing) folds nothing."""
        if not self.t_pack or not self.t_commit:
            return
        pre = "req." + kind + "."
        prof.fold(pre + "queue", self.t_pack - self.t0)
        if kind == "w":
            prof.fold(pre + "replicate", self.t_commit - self.t_pack)
            prof.fold(pre + "apply_wait", self.t_apply0 - self.t_commit)
            prof.fold(pre + "apply", self.t_done - self.t_apply0)
        else:
            prof.fold(pre + "confirm", self.t_commit - self.t_pack)
            prof.fold(pre + "complete", self.t_done - self.t_commit)
        prof.fold(pre + "launches", self.n_commit - self.n_pack + 1)
        prof.fold(pre + "n", 1)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


# mmap ring layout: a 64-byte header followed by `capacity` fixed-size
# slots. Each slot is [u64 seq | u32 len | payload-json]. The writer
# invalidates (seq=0), writes the payload, then seals (seq=n) LAST — a
# SIGKILL mid-write leaves exactly one unsealed slot and every other slot
# readable, and recovery orders sealed slots by seq. mmap stores survive
# process death (the pages live in the kernel's page cache), which is the
# whole point: `timeout -k`/pytest-timeout kills leave a readable timeline
# where the in-memory deque dies with the process.
_RING_MAGIC = b"DBTPUFR1"
_RING_HDR = struct.Struct("<8sIId")  # magic, capacity, slot_size, mono_off
_RING_HDR_SIZE = 64
_SLOT_HDR = struct.Struct("<QI")  # seq, payload length


def _truncated_payload(payload: bytes, limit: int) -> bytes:
    """Shrink an oversized event to a valid-JSON truncation marker that
    keeps the load-bearing identity fields (when, what, which group),
    shedding progressively if the slot is tiny."""
    try:
        d = json.loads(payload)
    except (ValueError, UnicodeDecodeError):
        return b'{"_truncated": true}'
    for keys, clip in (
        (("t", "event", "cluster", "node", "trace", "nodeid"), 160),
        (("t", "event", "cluster"), 80),
        (("event",), 40),
    ):
        keep = {
            k: (v[:clip] if isinstance(v, str) else v)
            for k, v in d.items()
            if k in keys
        }
        keep["_truncated"] = True
        out = json.dumps(keep, default=str, sort_keys=True).encode()
        if len(out) <= limit:
            return out
    return b'{"_truncated": true}'


class MmapRing:
    """Crash-persistent fixed-slot event ring (see layout note above).

    write() is: lock, invalidate slot, copy payload, seal — a few hundred
    nanoseconds on a warm page. Events are breadcrumb-rate (sampled or
    anomaly-only; the hot-path lint enforces it), so the eager
    json.dumps per event is fine here where it would not be on the step
    path."""

    def __init__(
        self, path: str, capacity: int = 4096, slot_size: int = 512
    ) -> None:
        self.path = path
        self.capacity = capacity
        self.slot_size = slot_size
        self.mono_offset = time.time() - time.monotonic()
        self._mu = threading.Lock()
        self._seq = 0
        size = _RING_HDR_SIZE + capacity * slot_size
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
        os.ftruncate(self._fd, size)
        self._mm = mmap.mmap(self._fd, size)
        hdr = _RING_HDR.pack(
            _RING_MAGIC, capacity, slot_size, self.mono_offset
        )
        self._mm[: len(hdr)] = hdr
        # zero the slot seals so a reused file never resurrects old events
        for i in range(capacity):
            off = _RING_HDR_SIZE + i * slot_size
            self._mm[off : off + 8] = b"\x00" * 8

    def write(self, payload: bytes) -> None:
        limit = self.slot_size - _SLOT_HDR.size
        if len(payload) > limit:
            # a raw byte cut would leave invalid JSON that recovery drops
            # as torn; degrade to a JSON-safe truncation marker instead so
            # the event (when + what kind) survives in the crash timeline
            payload = _truncated_payload(payload, limit)
        with self._mu:
            self._seq += 1
            seq = self._seq
            off = _RING_HDR_SIZE + ((seq - 1) % self.capacity) * self.slot_size
            mm = self._mm
            mm[off : off + 8] = b"\x00" * 8  # invalidate
            mm[off + 8 : off + 12] = struct.pack("<I", len(payload))
            mm[off + 12 : off + 12 + len(payload)] = payload
            mm[off : off + 8] = struct.pack("<Q", seq)  # seal

    def flush(self) -> None:
        try:
            # lint: allow(locks/guarded-state) signal-safe: SIGTERM/atexit
            # may fire while a writer holds _mu — taking it here could
            # deadlock the dying process; a racing flush is an idempotent
            # kernel page sync
            self._mm.flush()
        except (ValueError, OSError):
            pass

    def close(self) -> None:
        with self._mu:
            try:
                self._mm.flush()
                self._mm.close()
            except (ValueError, OSError):
                pass
            try:
                os.close(self._fd)
            except OSError:
                pass


def read_mmap_ring(path: str) -> Tuple[dict, List[dict]]:
    """Recover a (possibly SIGKILL'd) process's mmap ring: returns
    (meta, events) with events ordered by their seal sequence. Unsealed or
    torn slots (the one a kill interrupted, or an oversized truncated
    payload) are skipped — the rest of the timeline stays valid."""
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < _RING_HDR_SIZE:
        raise ValueError(f"{path}: not a flight ring (too small)")
    magic, capacity, slot_size, mono_offset = _RING_HDR.unpack_from(raw, 0)
    if magic != _RING_MAGIC:
        raise ValueError(f"{path}: not a flight ring (bad magic)")
    slots = []
    for i in range(capacity):
        off = _RING_HDR_SIZE + i * slot_size
        if off + _SLOT_HDR.size > len(raw):
            break
        seq, n = _SLOT_HDR.unpack_from(raw, off)
        if seq == 0 or n > slot_size - _SLOT_HDR.size:
            continue
        try:
            d = json.loads(raw[off + 12 : off + 12 + n])
        except (ValueError, UnicodeDecodeError):
            continue  # torn slot: the write this kill interrupted
        slots.append((seq, d))
    slots.sort(key=lambda s: s[0])
    # capacity/slot_size ride the meta so readers (tools.doctor's ring
    # report, tools.top --history) can say how much timeline the ring
    # COULD hold vs what it did — a full ring means older samples were
    # overwritten, an honesty caveat every diagnosis should carry
    meta = {
        "mono_offset": mono_offset,
        "source": os.path.basename(path),
        "capacity": int(capacity),
        "slot_size": int(slot_size),
    }
    return meta, [d for _, d in slots]


# the span store holds at least 60 s of a loop that launches 20 times a
# second and leaves some 25 spans a launch (12 phases, the seam's four
# sub-spans, a deliver per send phase)
SPAN_CAPACITY = 32768


class FlightRecorder:
    """Bounded ring of structured events with monotonic timestamps.

    append (record) is one deque.append of a small tuple — GIL-atomic, no
    lock — so producers on engine/transport/apply threads pay nanoseconds.
    The ring bounds memory: a runaway event source overwrites the oldest
    breadcrumbs instead of growing without limit.

    The profilers' `phase_span` events have a bounded store of their own
    (span()), so that no other event can evict them; a span that the
    full store sheds before any dump has returned it is counted in
    `spans_dropped` (one that a poller has had is retired, not lost).
    `open_spans` holds the span each
    begin()-style profiler has running, which dumps show with the end it
    has so far: a poller sees the loop's present, and a hang dump names
    the phase the loop is stuck in.

    Every event carries a `cluster` field (0 = host-level: breakers,
    send queues, fairness) so dumps filter server-side by Raft group.
    attach_mmap() tees every record into a crash-persistent MmapRing so a
    SIGKILL'd process still leaves a readable timeline (read_mmap_ring)."""

    __slots__ = ("_buf", "_ring", "mono_offset", "_spans", "_span_total",
                 "_span_seen", "spans_dropped", "open_spans")

    def __init__(
        self, capacity: int = 8192, span_capacity: int = SPAN_CAPACITY
    ) -> None:
        self._buf: deque = deque(maxlen=capacity)
        self._spans: deque = deque(maxlen=span_capacity)
        self._span_total = 0  # spans ever stored
        self._span_seen = 0  # of those, how many a dump has returned
        self.spans_dropped = 0
        self.open_spans: Dict[int, Tuple[str, str, float]] = {}
        self._ring: Optional[MmapRing] = None
        # wall-minus-monotonic at init: dumps carry it so the timeline CLI
        # can merge rings/dumps from different processes (each process's
        # monotonic clock has an arbitrary base) onto one wall-clock axis
        self.mono_offset = time.time() - time.monotonic()

    def record(self, event: str, **fields) -> None:
        if "cluster" not in fields:
            fields["cluster"] = 0  # host-level event
        t = time.monotonic()
        self._buf.append((t, event, fields))
        if self._ring is not None:
            d = {"t": round(t, 6), "event": event}
            d.update(fields)
            self._tee(d)

    def span(self, engine: str, phase: str, t0: float, t1: float) -> bool:
        """Store one finished profiler span as a `phase_span` event: `t`
        is the instant it ended and `t0` the instant it began, both as
        the profiler read them, `dur` their difference. True when the
        store was full and shed, to make room, a span no dump had seen."""
        d = {
            "t": t1, "event": "phase_span", "cluster": 0, "engine": engine,
            "phase": phase, "dur": t1 - t0, "t0": t0,
        }
        spans = self._spans
        # the oldest span stored is number _span_total - maxlen
        shed = (
            len(spans) == spans.maxlen
            and self._span_total - spans.maxlen >= self._span_seen
        )
        if shed:
            self.spans_dropped += 1
        self._span_total += 1
        spans.append(d)
        if self._ring is not None:
            self._tee(d)
        return shed

    def _tee(self, d: dict) -> None:
        ring = self._ring
        if ring is not None:
            try:
                ring.write(json.dumps(d, default=str, sort_keys=True).encode())
            except Exception:
                pass  # persistence must never break the producer

    def __len__(self) -> int:
        return len(self._buf) + len(self._spans)

    def reset(self) -> None:
        self._buf.clear()
        self._spans.clear()
        self._span_total = self._span_seen = self.spans_dropped = 0

    # ------------------------------------------------- persistent backing
    def attach_mmap(
        self, path: str, capacity: int = 4096, slot_size: int = 512
    ) -> MmapRing:
        """Tee every subsequent record() into a crash-persistent ring at
        `path`. Idempotent for the same path — a NodeHost and the test
        harness may both request it. A PRE-EXISTING ring file rotates to
        `<path>.prev` first: the previous (possibly SIGKILL'd) process's
        timeline is the artifact this feature exists to preserve, so a
        restart's auto-attach (DRAGONBOAT_FLIGHT_RING, the pytest session
        ring) must never truncate it — recover it any time from the .prev
        file with read_mmap_ring. Rotation also keeps two co-located
        processes handed the same path on separate inodes (the first
        keeps writing its now-renamed mapping) instead of interleaving
        seq counters in one file."""
        ring = self._ring
        if ring is not None and ring.path == path:
            return ring
        try:
            with open(path, "rb") as f:
                had_ring = f.read(len(_RING_MAGIC)) == _RING_MAGIC
            if had_ring:
                os.replace(path, path + ".prev")
        except OSError:
            pass  # no previous ring (or unreadable): nothing to preserve
        new = MmapRing(path, capacity=capacity, slot_size=slot_size)
        self._ring, old = new, ring
        if old is not None:
            old.close()
        return new

    def detach_mmap(self) -> None:
        ring, self._ring = self._ring, None
        if ring is not None:
            ring.close()

    def flush(self) -> None:
        ring = self._ring
        if ring is not None:
            ring.flush()

    # ------------------------------------------------------------- dumps
    @staticmethod
    def _snapshot(buf: deque) -> list:
        """Point-in-time copy of a deque that is safe against concurrent
        appends: under free threading list(deque) can raise RuntimeError
        ("deque mutated during iteration") — retry until a clean pass
        (appends are tiny, so a clean pass comes within a few tries)."""
        while True:
            try:
                return list(buf)
            except RuntimeError:
                continue

    def dump(
        self,
        cluster_id: Optional[int] = None,
        trace_id: Optional[int] = None,
        event: Optional[str] = None,
    ) -> List[dict]:
        """Events oldest-first as plain dicts (t = monotonic seconds).
        Server-side filters: cluster_id matches the event's `cluster`
        field, trace_id the `trace` field, event the event name. Spans
        are host-level (cluster 0) and carry no trace id; one that still
        runs is marked `open` and ends at this instant."""
        out = []
        if event != "phase_span":
            for t, ev, fields in self._snapshot(self._buf):
                if event is not None and ev != event:
                    continue
                if cluster_id is not None and fields.get("cluster") != cluster_id:
                    continue
                if trace_id is not None and fields.get("trace") != trace_id:
                    continue
                d = {"t": round(t, 6), "event": ev}
                if fields:
                    d.update(fields)
                out.append(d)
        if (event is None or event == "phase_span") and trace_id is None \
                and not cluster_id:
            n = len(out)
            seen = self._span_total  # read first: the copy holds these
            out.extend(dict(d) for d in self._snapshot(self._spans))
            self._span_seen = seen
            now = time.monotonic()
            for engine, phase, t0 in list(self.open_spans.values()):
                out.append({
                    "t": now, "event": "phase_span", "cluster": 0,
                    "engine": engine, "phase": phase, "dur": now - t0,
                    "t0": t0, "open": True,
                })
            if n:
                out.sort(key=lambda d: d["t"])
        return out

    def to_jsonl(self, meta=None, **filters) -> str:
        """JSONL dump; pass meta=True (or a dict of extra meta fields,
        e.g. {"source": "node1"}) to prepend a `_meta` line carrying the
        mono->wall offset the timeline CLI uses to merge multi-process
        dumps onto one clock."""
        lines = []
        if meta:
            m = {"event": "_meta", "mono_offset": round(self.mono_offset, 6)}
            if isinstance(meta, dict):
                m.update(meta)
            lines.append(json.dumps(m, default=str, sort_keys=True))
        lines.extend(
            json.dumps(d, default=str, sort_keys=True)
            for d in self.dump(**filters)
        )
        return "\n".join(lines)


# process-global recorder: every subsystem appends here so a test failure
# dump needs no plumbing — one timeline covers all NodeHosts in the process
# (events carry their own identity fields)
_global_recorder = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _global_recorder


__all__ = [
    "Sample",
    "Profiler",
    "SPAN_CAPACITY",
    "LatencySampler",
    "LatencyTrace",
    "FlightRecorder",
    "MmapRing",
    "flight_recorder",
    "mint_trace_id",
    "read_mmap_ring",
]
