"""Autoscale policy simulation: replay a load timeline against the
controller's policy without a cluster.

The reference can only tune Knative knobs by deploying and measuring
(sweeps/autoscale-sweep.sh — hours per point); the in-repo controller's
policy core is a pure function (`controller.desired_replicas`), so the
whole what-if space replays in milliseconds: recorded run-dir traffic (or
a synthetic arrival pattern) drives a fluid queue model, the controller
polls simulated fleet signals on its real cadence, and scale-ups apply
after a provisioning delay — minutes on TPU pools (docs/TOPOLOGY.md), the
thing that actually decides whether a policy survives a burst.

Model (deliberately simple, stated so the numbers are interpretable):
- each request arrives at its timestamp carrying `tokens_out` tokens of
  decode work (or 1 unit when the timeline has no token counts);
- the fleet serves FIFO at ``replicas x rate`` work-units/s; a request
  completes when its work is drained;
- duty cycle = capacity utilization of the step, queue depth = requests
  waiting or in service beyond instantaneous capacity — the same two
  signals the live /metrics endpoint feeds the controller;
- scale-up decisions become capacity only after ``provision_delay_s``
  (pending replicas are tracked with ready times); scale-down is
  immediate (killing a pod is fast, providing one is not).

Outputs: the controller's own decision log plus a per-step series CSV and
a summary (peak/mean queue, request p50/p95 wait, replica-seconds = the
cost proxy, unserved backlog at end) -> ``autoscale_sim.json`` in the run
dir, which the report layer's decision-timeline section can plot against
the load.
"""

from __future__ import annotations

import argparse
import csv
import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

from kserve_vllm_mini_tpu.autoscale.controller import (
    Controller,
    PolicyConfig,
    Signals,
)


@dataclass
class SimConfig:
    rate_per_replica: float = 2000.0   # work-units/s (tokens/s/chip scale)
    poll_interval_s: float = 15.0
    provision_delay_s: float = 180.0   # TPU pool cold start: minutes
    initial_replicas: int = 1
    drain_s: float = 120.0             # sim tail after the last arrival


@dataclass
class SimResult:
    steps: list[dict[str, Any]] = field(default_factory=list)
    decisions: list[dict[str, Any]] = field(default_factory=list)
    summary: dict[str, Any] = field(default_factory=dict)


def load_timeline_from_rundir(run_dir: str) -> list[tuple[float, float]]:
    """(arrival_ts, work_units) per request from a recorded requests.csv,
    times rebased to 0. Work = tokens_out when recorded (>0), else 1."""
    path = Path(run_dir) / "requests.csv"
    rows: list[tuple[float, float]] = []
    with path.open() as f:
        for rec in csv.DictReader(f):
            try:
                ts = float(rec.get("scheduled_ts") or rec.get("start_ts") or 0)
            except ValueError:
                continue
            if ts <= 0:
                continue
            try:
                work = float(rec.get("tokens_out") or 0)
            except ValueError:
                work = 0.0
            rows.append((ts, work if work > 0 else 1.0))
    if not rows:
        raise ValueError(f"no usable rows in {path}")
    rows.sort()
    t0 = rows[0][0]
    return [(ts - t0, w) for ts, w in rows]


def synthetic_timeline(
    pattern: str, requests: int, duration_s: float,
    work_per_request: float = 64.0, seed: int = 42,
) -> list[tuple[float, float]]:
    """Synthetic arrivals through the load generator's own pattern engine
    (loadgen/arrivals.py) so the sim and a real run share traffic shapes."""
    from kserve_vllm_mini_tpu.loadgen.arrivals import generate_arrival_times

    times = generate_arrival_times(pattern, requests, duration_s, seed=seed)
    return [(t, work_per_request) for t in times]


def simulate(
    timeline: list[tuple[float, float]],
    sim: Optional[SimConfig] = None,
    policy: Optional[PolicyConfig] = None,
) -> SimResult:
    sim = sim or SimConfig()
    policy = policy or PolicyConfig()
    res = SimResult()

    from collections import deque

    # fluid queue: FIFO of [remaining_work, arrival_ts]; completed requests
    # record their wait (arrival -> fully served). deque: a deep
    # underprovisioned backlog would make list.pop(0) O(n²)
    queue: "deque[list[float]]" = deque()
    waits: list[float] = []
    clock = {"t": 0.0}

    # fleet state: active replicas + pending scale-ups with ready times
    state = {"active": sim.initial_replicas}
    pending: list[tuple[float, int]] = []   # (ready_ts, target_count)
    # signals computed by the previous sim step, handed to the controller
    last_sig = {"duty": 0.0, "queue": 0}

    def now_fn() -> float:
        return clock["t"]

    def scaler(n: int) -> None:
        # ANY new target invalidates in-flight scale-ups above it — also
        # an intermediate shrink issued while capacity is still
        # provisioning (active < n < old pending), or the stale pendings
        # would land later and pin the fleet above desired
        pending[:] = [(ts, t) for ts, t in pending if t <= n]
        if n <= state["active"]:
            state["active"] = n          # shrink: immediate
        else:
            pending.append((clock["t"] + sim.provision_delay_s, n))

    def signal_fn() -> Signals:
        return Signals(
            duty_cycle=last_sig["duty"],
            queue_depth=float(last_sig["queue"]),
            ts=clock["t"],
            valid=True,
        )

    ctl = Controller(
        signal_fn, scaler, policy,
        initial_replicas=sim.initial_replicas, now_fn=now_fn,
    )

    horizon = (timeline[-1][0] if timeline else 0.0) + sim.drain_s
    dt = sim.poll_interval_s
    n_steps = max(int(math.ceil(horizon / dt)), 1)
    arr_idx = 0
    replica_seconds = 0.0

    for step in range(n_steps):
        t_end = (step + 1) * dt
        # provisioned capacity lands when ready
        for ready_ts, target in sorted(pending):
            if ready_ts <= t_end:
                state["active"] = max(state["active"], target)
        pending[:] = [(ts, t) for ts, t in pending if ts > t_end]

        # arrivals within the step
        while arr_idx < len(timeline) and timeline[arr_idx][0] < t_end:
            ts, work = timeline[arr_idx]
            queue.append([work, ts])
            arr_idx += 1

        # serve FIFO with this step's capacity
        capacity = state["active"] * sim.rate_per_replica * dt
        served = 0.0
        while queue and capacity > 0:
            need = queue[0][0]
            take = min(need, capacity)
            queue[0][0] -= take
            capacity -= take
            served += take
            if queue[0][0] <= 1e-9:
                _, arrived = queue.popleft()
                waits.append(t_end - arrived)
        total_capacity = state["active"] * sim.rate_per_replica * dt
        last_sig["duty"] = min(served / total_capacity, 1.0) if total_capacity else 0.0
        last_sig["queue"] = len(queue)
        replica_seconds += state["active"] * dt

        clock["t"] = t_end
        ctl.step()
        res.steps.append({
            "t": t_end,
            "replicas_active": state["active"],
            "replicas_desired": ctl.replicas,
            "pending_ups": len(pending),
            "queue": len(queue),
            "duty": round(last_sig["duty"], 4),
        })

    waits_sorted = sorted(waits)

    def pct(p: float) -> float:
        if not waits_sorted:
            return 0.0
        i = min(int(p * (len(waits_sorted) - 1)), len(waits_sorted) - 1)
        return waits_sorted[i]

    res.decisions = ctl.decisions
    res.summary = {
        "requests": len(timeline),
        "completed": len(waits),
        "unserved_at_end": len(queue),
        "peak_queue": max((s["queue"] for s in res.steps), default=0),
        "wait_p50_s": round(pct(0.50), 2),
        "wait_p95_s": round(pct(0.95), 2),
        "replica_seconds": round(replica_seconds, 1),
        "peak_replicas": max((s["replicas_active"] for s in res.steps), default=0),
        "final_replicas": state["active"],
        "provision_delay_s": sim.provision_delay_s,
    }
    return res


# -- CLI ---------------------------------------------------------------------

def register(parser: argparse.ArgumentParser) -> None:
    src = parser.add_mutually_exclusive_group(required=True)
    src.add_argument("--run-dir", help="Replay a recorded requests.csv timeline")
    from kserve_vllm_mini_tpu.loadgen.arrivals import PATTERNS

    src.add_argument("--pattern", choices=sorted(PATTERNS),
                     help="Synthesize arrivals with the loadgen's pattern engine")
    parser.add_argument("--requests", type=int, default=200,
                        help="Synthetic request count (--pattern)")
    parser.add_argument("--duration", type=float, default=300.0,
                        help="Synthetic timeline seconds (--pattern)")
    parser.add_argument("--work-per-request", type=float, default=64.0,
                        help="Work units (output tokens) per synthetic request")
    parser.add_argument("--rate-per-replica", type=float, default=2000.0,
                        help="Serving rate per replica, work-units/s "
                             "(tokens/s/chip; measure it — PERF.md)")
    parser.add_argument("--provision-delay", type=float, default=180.0,
                        help="Seconds before a scale-up becomes capacity "
                             "(TPU pools provision in minutes)")
    parser.add_argument("--interval", type=float, default=15.0)
    parser.add_argument("--drain", type=float, default=None,
                        help="Sim tail seconds after the last arrival "
                             "(default: max(120, 2x provisioning delay) so "
                             "late-landing capacity and its drain are "
                             "always observed)")
    parser.add_argument("--min", type=int, default=1)
    parser.add_argument("--max", type=int, default=8)
    parser.add_argument("--target-duty", type=float, default=0.75)
    parser.add_argument("--target-queue", type=float, default=4.0)
    parser.add_argument("--initial-replicas", type=int, default=1)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--output", default=None,
                        help="Write autoscale_sim.json here (default: "
                             "<run-dir>/autoscale_sim.json or stdout only)")


def run(args: argparse.Namespace) -> int:
    if args.run_dir:
        timeline = load_timeline_from_rundir(args.run_dir)
    else:
        timeline = synthetic_timeline(
            args.pattern, args.requests, args.duration,
            work_per_request=args.work_per_request, seed=args.seed,
        )
    res = simulate(
        timeline,
        SimConfig(
            rate_per_replica=args.rate_per_replica,
            poll_interval_s=args.interval,
            provision_delay_s=args.provision_delay,
            initial_replicas=args.initial_replicas,
            drain_s=(args.drain if args.drain is not None
                     else max(120.0, 2.0 * args.provision_delay)),
        ),
        PolicyConfig(
            min_replicas=args.min, max_replicas=args.max,
            target_duty=args.target_duty,
            target_queue_per_replica=args.target_queue,
        ),
    )
    print(json.dumps(res.summary, indent=2))
    out = args.output
    if out is None and args.run_dir:
        out = str(Path(args.run_dir) / "autoscale_sim.json")
    if out:
        Path(out).write_text(json.dumps({
            "summary": res.summary,
            "steps": res.steps,
            "decisions": res.decisions,
        }, indent=2))
        print(f"wrote {out}")
    return 0
