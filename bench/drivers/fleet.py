"""The `fleet` driver: the fleet service under open-loop packet traffic.

Set-up makes the fleet's packets from the seed (`fleet_traffic`), builds
the service as `repro.launch.serve_fleet` does (incident tier on),
compiles the fused tick for every padded job count and sync profile the
stream can reach, and runs the stream for `WARMUP_PERIODS` periods.  The
window then offers one window per job per period at each job's phase:
as soon as its previous micro-batch is done, the service takes whatever
has fallen due (`submit_many(..., refresh=True)`, then `route(k)`, the
operator's top-k view), and `tick()` runs once per period.  A window's
latency runs from the time it was due to the end of the route answer that
reflects it.  Windows due before the close but not yet served are served
after it, and count in the tail.

After the window a sample of jobs, drawn from the seed, is checked: the
kernel's shares, gains, leader and what-if matrix against `fleet_oracle`
on the window the job last sent, and every faulted job's route against
the injected (stage, rank).
"""
from __future__ import annotations

import bisect
import contextlib
import time

import numpy as np

import flops
import fleet_oracle as oracle
import fleet_traffic as ft
import harness

WARMUP_PERIODS = 3   # periods served in set-up: every job has sent windows and been ticked


def make_service(config: dict):
    from repro.fleet import FleetService
    from repro.incidents import IncidentEngine

    return FleetService(
        window_capacity=config["window_steps"], evict_after=2, degrade_after=2,
        regime_windows=4, incidents=IncidentEngine(), obs=True,
    )


def sync_index(profile: str) -> tuple:
    return tuple(ft.STAGES.index(s) for s in ft.SYNC_PROFILES[profile])


def warm_ticks(config: dict, fleet: ft.Fleet) -> int:
    """Compile the fused tick for every padded job count each sync
    profile's cohort can reach; returns the number of shapes."""
    import jax

    from repro.kernels.frontier import fused_fleet_tick

    shapes = 0
    n, r, s = config["window_steps"], config["ranks"], len(ft.STAGES)
    for profile in ft.SYNC_PROFILES:
        count = fleet.profiles.count(profile)
        if not count:
            continue
        top = 1 << (count - 1).bit_length()
        p = 1
        while p <= top:
            jax.block_until_ready(fused_fleet_tick(
                np.zeros((p, n, r, s), np.float32), sync_stages=sync_index(profile),
                with_regimes=False))
            shapes += 1
            p *= 2
    return shapes


class Stream:
    """Drives the service through the schedule, open loop."""

    def __init__(self, service, sched: ft.Schedule, fleet: ft.Fleet, config: dict,
                 route_k: int, traced: bool):
        self.service, self.sched, self.fleet = service, sched, fleet
        self.route_k = route_k
        self.dims = (config["window_steps"], config["ranks"], len(ft.STAGES))
        self.next = 0                        # index of the next packet to serve
        self.next_tick = sched.period_s
        self.last_wire: dict = {}
        self.ann = (__import__("jax").profiler.TraceAnnotation if traced
                    else lambda name: contextlib.nullcontext())

    def _serve(self, lo: int, hi: int, origin: float, log: dict) -> None:
        sched, ids = self.sched, self.fleet.job_ids
        batch = [(ids[sched.job[k]], sched.wire[k]) for k in range(lo, hi)]
        with self.ann("bench.submit_many"):
            accepted = self.service.submit_many(batch, refresh=True)
        with self.ann("bench.route"):
            self.service.route(self.route_k)
        done = time.perf_counter() - origin
        log["latency"].extend(done - sched.due[lo:hi])
        log["due"].extend(sched.due[lo:hi])
        log["done"].extend([done] * (hi - lo))
        log["refused"] += len(batch) - accepted
        cohort: dict = {}
        for k in range(lo, hi):
            j = int(sched.job[k])
            self.last_wire[j] = sched.wire[k]
            cohort.setdefault(self.fleet.profiles[j], set()).add(j)
        for jobs in cohort.values():
            log["tick_flops"] += flops.tick_flops(len(jobs), *self.dims)
            log["tick_bytes"] += flops.tick_bytes(len(jobs), *self.dims)

    def run(self, t_from: float, t_to: float) -> dict:
        """Serve every packet due in [t_from, t_to), the stream's clock
        starting now at t_from; packets still due at t_to are served
        after it."""
        sched = self.sched
        origin = time.perf_counter() - t_from
        end = bisect.bisect_left(sched.due, t_to)
        log = {"latency": [], "due": [], "done": [], "refused": 0, "tick_flops": 0.0,
               "tick_bytes": 0.0, "ticks": [], "tick_max": 0.0}
        while True:
            now = time.perf_counter() - origin
            if now >= t_to:
                break
            if now >= self.next_tick:
                t = time.perf_counter()
                with self.ann("bench.tick"):
                    self.service.tick()
                log["tick_max"] = max(log["tick_max"], time.perf_counter() - t)
                self.next_tick += sched.period_s
                log["ticks"].append((phase_sums(self.service), self.next))
                continue
            hi = min(bisect.bisect_right(sched.due, now), end)
            if hi == self.next:
                wake = min(sched.due[self.next] if self.next < end else t_to,
                           self.next_tick, t_to)
                time.sleep(max(0.0, wake - now))
                continue
            self._serve(self.next, hi, origin, log)
            self.next = hi
        if self.next < end:
            self._serve(self.next, end, origin, log)
            self.next = end
        return log


def check_answers(service, stream: Stream, fleet: ft.Fleet, config: dict, seed: int,
                  precision: str = "f32") -> dict:
    """The numbers `correct` is decided on (see the module docstring).
    `precision="bf16"` puts the oracle in bfloat16 in the service's place:
    the control."""
    rng = np.random.default_rng([int(seed) % 2**64, 3])
    served = sorted(stream.last_wire)
    pick = rng.choice(served, size=min(config["sample_jobs"], len(served)), replace=False)
    kernel_gap, leader_mismatch = 0.0, 0
    for j in sorted(int(x) for x in pick):
        _, window = oracle.decode_window(stream.last_wire[j])
        sync = sync_index(fleet.profiles[j])
        want = oracle.tick(window, sync)
        if precision == "f32":
            job = service.registry.get(fleet.job_ids[j])
            got = {"shares": job.kernel_shares, "gains": job.kernel_gains,
                   "whatif": job.whatif, "leader": job.kernel_leader}
            if got["shares"] is None or got["whatif"] is None:
                kernel_gap = float("inf")
                continue
        else:
            low = oracle.tick(window, sync, precision)
            got = {**low, "leader": oracle.top_leader(low)}
        kernel_gap = max(kernel_gap, *(oracle.gap(got[k], want[k])
                                       for k in ("shares", "gains", "whatif")))
        leader_mismatch += int(got["leader"] != oracle.top_leader(want))
    out = {"kernel_gap": kernel_gap, "leader_mismatch": leader_mismatch}
    if service is not None:
        routes = {r.job_id: (r.stage, r.rank) for r in service.route(len(service.registry))}
        out["route_misses"] = sum(routes.get(job) != (ft.FAULT_STAGE, rank)
                                  for job, rank in fleet.fault_rank.items())
    return out


def phase_sums(service) -> float:
    """Seconds the service's own clock has charged to decoding and
    folding, over the ticks it has closed (a tick closes the phases of
    the calls since the one before)."""
    m = service.obs.metrics
    return sum(m.histogram(f"phase_seconds.{p}").sum_ns
               for p in ("tick.decode", "tick.regimes")) * 1e-9


def run(ctx) -> dict:
    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    fleet = ft.build_fleet(config, seed)
    pool = ft.encode_pool(fleet, config)
    period = config["jobs"] / traffic["rate_windows_per_s"]
    warm = WARMUP_PERIODS * period
    sched = ft.schedule(fleet, pool, config, traffic, seed, warm + ctx.seconds)
    service = make_service(config)
    shapes = warm_ticks(config, fleet)
    stream = Stream(service, sched, fleet, config, traffic["route_k"], ctx.trace)
    stream.run(0.0, warm)
    setup_s = ctx.clock.now()
    compiles0 = ctx.compiles.programs
    first = stream.next
    with ctx.window() as annotate:
        with annotate:
            log = stream.run(warm, warm + ctx.seconds)
    in_window = ctx.compiles.programs - compiles0
    (s0, n0), (s1, n1) = log["ticks"][0], log["ticks"][-1]
    memory = harness.memory_peak(ctx.devices)
    windows = stream.next - first
    close = warm + ctx.seconds
    routed = sum(1 for t in log["done"] if t <= close)
    lat = np.asarray(log["latency"])
    due = np.asarray(log["due"]) - warm
    quarter = [float(np.median(lat[(due >= q * ctx.seconds / 4) & (due < (q + 1) * ctx.seconds / 4)]))
               if np.any((due >= q * ctx.seconds / 4) & (due < (q + 1) * ctx.seconds / 4)) else None
               for q in range(4)]
    worst = int(np.argmax(lat))
    readings = check_answers(service, stream, fleet, config, seed)
    diag = {"windows": windows, "routed_in_window": routed, "ticks": len(log["ticks"]),
            "period_s": period, "warm_shapes": shapes, "compiles_in_window": in_window,
            "latency_median_ms": float(np.median(lat) * 1e3), "refused": log["refused"],
            "latency_median_by_quarter_ms": [q and q * 1e3 for q in quarter],
            "worst_ms": float(lat[worst] * 1e3), "worst_due_s": float(due[worst]),
            "tick_max_ms": log["tick_max"] * 1e3,
            "offered_per_s": traffic["rate_windows_per_s"]}
    harness.say("fleet", **diag)
    return {
        "setup_s": setup_s,
        "end_to_end": {
            "route_p95_ms": harness.p95(lat) * 1e3,
            "routed_windows_per_s": routed / ctx.seconds,
        },
        "attempted": windows,
        "failed": log["refused"],
        "checks": {k: harness.check(readings[k], config["limits"][k])
                   for k in config["limits"]},
        "compiles_in_window": in_window,
        "memory_peak_bytes": memory,
        "diagnostics": diag,
        "layer": {
            "ingest_s": s1 - s0,
            "ingest_windows": n1 - n0,
            "tick_flops": log["tick_flops"],
            "tick_bytes": log["tick_bytes"],
        },
    }


def control(ctx) -> dict:
    """Readings of the control, the oracle in bfloat16 put in the
    service's place, on a sample of the jobs' windows."""
    config, seed = ctx.config, ctx.seed
    fleet = ft.build_fleet(config, seed)
    pool = ft.encode_pool(fleet, config)

    class Last:
        last_wire = {j: packets[-1] for j, packets in enumerate(pool)}

    got = check_answers(None, Last, fleet, config, seed, precision="bf16")
    return {"control_bf16": {k: got[k] for k in ("kernel_gap", "leader_mismatch")}}
