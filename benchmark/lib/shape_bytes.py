"""Bytes the step kernel must move, from the engine's shapes alone.

The kernel is integer and memory-bound, so its roof is bytes. This is the
benchmark's own count of the planes the program declares
(ops/state.py: RaftTensors, Inbox, StepOutput, RoutePlan), written as
shapes so that a change to the program cannot move the yardstick;
benchmark/tests holds it to jax.eval_shape of the program's own arrays.

G lanes, P peer slots, W log window, K inbox depth, E entries per
message, R read-index depth. i32/u32 = 4 bytes, bool = 1.
"""
from __future__ import annotations

_CTR = 8  # event-counter columns of StepOutput.counters


def state_bytes(G, P, W, R) -> int:
    per_lane = (
        27 * 4 + 9  # [G] planes: 27 of i32/u32, 9 of bool
        + P * (4 * 4 + 7)  # [G,P]: match next rstate snap_sent; 7 bool
        + W * (4 + 1)  # [G,W]: log_term, log_is_cc
        + R * 4 * 4  # [G,R]: ri_ctx ri_ctx2 ri_index ri_acks
    )
    return G * per_lane


def inbox_bytes(G, K, E) -> int:
    per_row = 9 * 4 + 1 + E * (4 + 1)  # 9 i32 + reject; entry term + cc
    return G * K * per_row


def output_bytes(G, P, K, R) -> int:
    per_lane = (
        20 * 4 + 5  # [G] planes: 20 of i32, 5 of bool
        + P * 10 * 4  # [G,P]: 8 send planes, match, rstate
        + K * (8 * 4 + 1)  # [G,K]: 6 resp i32 + reject, prop/rep base
        + R * 3 * 4  # [G,R]: ready_ctx ready_ctx2 ready_index
        + _CTR * 4
    )
    return G * per_lane


def plan_bytes(G, P, K, R) -> int:
    return G * (4 * P + K + R)  # RoutePlan: all bool


def launch_bytes(G, P, W, K, E, R, steps_per_sync: int = 1) -> int:
    """Bytes one kernel launch must read and write: the state once each
    way, the inbox and the tick plane in, one StepOutput out per protocol
    step. At steps_per_sync > 1 the launch also reads and writes the
    residual inbox, reads the route and base-delta planes, and writes one
    RoutePlan per step and the residual occupancy."""
    total = 2 * state_bytes(G, P, W, R) + inbox_bytes(G, K, E) + 4 * G
    total += steps_per_sync * output_bytes(G, P, K, R)
    if steps_per_sync > 1:
        total += 2 * inbox_bytes(G, K, E) + 2 * 4 * G * P + 4 * G
        total += steps_per_sync * plan_bytes(G, P, K, R)
    return total
