"""Open-loop evidence-packet traffic for the `fleet` driver, from the seed.

The jobs are the paper's hidden-rank fleet: six coarse stages with the
DDP base means (about 208 ms a step), sync profiles rotating DDP / FSDP /
ZeRO-1, and every `fault_every`-th job carrying a host delay in
`data.next_wait` on one rank drawn from the seed.  Stage durations come
from a copy of `repro.sim.simulate` (lognormal jitter, group release at
the end of each barrier stage), vectorised over jobs.  Each job's
windows are labelled by the program's client side (`WindowAggregator`)
and encoded as the wire packets its ranks would ship (SFP2, int8).

Every job reports one window per period, at a phase of its own drawn
from the seed; the period is jobs / rate.  A job cycles through
`pool_windows` distinct windows, each sent with the next window number
and first step, so the service folds one continuous step history.
"""
from __future__ import annotations

import dataclasses
import json
import struct

import numpy as np

STAGES = (
    "data.next_wait",
    "model.fwd_loss_cpu_wall",
    "model.backward_cpu_wall",
    "callbacks.cpu_wall",
    "optim.step_cpu_wall",
    "step.other_cpu_wall",
)
BASE_S = (0.012, 0.055, 0.105, 0.012, 0.022, 0.002)
SYNC_PROFILES = {
    "ddp": ("model.backward_cpu_wall",),
    "fsdp": ("model.fwd_loss_cpu_wall", "model.backward_cpu_wall"),
    "zero1": ("model.backward_cpu_wall", "optim.step_cpu_wall"),
}
SHARED_HOST = "shared-0"
JITTER = 0.02
FAULT_STAGE = "data.next_wait"


@dataclasses.dataclass
class Fleet:
    job_ids: list
    profiles: list            # sync profile name per job
    fault_rank: dict          # job id -> injected rank (faulted jobs only)
    hosts: list               # per-job rank -> host names
    durations: np.ndarray     # [J, pool * N, R, S] float64


def simulate(rng, sync_mask: np.ndarray, ranks: int, steps: int,
             fault_rank: np.ndarray, delay_s: float) -> np.ndarray:
    """Stage spans [J, steps, R, S] of J jobs: each rank's clock runs
    through the stages; a barrier stage ends at the group's latest
    arrival, and the wait is charged to it.  `fault_rank[j] >= 0` adds
    `delay_s` to that rank's first stage every step."""
    jobs, s_count = sync_mask.shape
    d = np.zeros((jobs, steps, ranks, s_count))
    clock = np.zeros((jobs, ranks))
    faulted = np.flatnonzero(fault_rank >= 0)
    for t in range(steps):
        for si in range(s_count):
            work = BASE_S[si] * rng.lognormal(0.0, JITTER, size=(jobs, ranks))
            if STAGES[si] == FAULT_STAGE:
                work[faulted, fault_rank[faulted]] += delay_s
            arrival = clock + work
            sync = sync_mask[:, si]
            release = arrival.max(axis=1, keepdims=True)
            arrival = np.where(sync[:, None], release, arrival)
            d[:, t, :, si] = arrival - clock
            clock = arrival
    return d


def build_fleet(config: dict, seed: int) -> Fleet:
    rng = np.random.default_rng([int(seed) % 2**64, 1])
    jobs, ranks, n = config["jobs"], config["ranks"], config["window_steps"]
    names = list(SYNC_PROFILES)
    profiles = [names[j % len(names)] for j in range(jobs)]
    job_ids = [f"job-{j:04d}-{profiles[j]}" for j in range(jobs)]
    fault = np.full(jobs, -1)
    for j in range(0, jobs, config["fault_every"]):
        fault[j] = int(rng.integers(ranks))
    mask = np.array([[s in SYNC_PROFILES[p] for s in STAGES] for p in profiles])
    d = simulate(rng, mask, ranks, config["pool_windows"] * n, fault,
                 config["delay_ms"] / 1e3)
    hosts = []
    for j in range(jobs):
        h = [f"h{j}-{r // config['ranks_per_host']}" for r in range(ranks)]
        if config["placement"] == "shared" and fault[j] >= 0:
            h[fault[j]] = SHARED_HOST
        hosts.append(tuple(h))
    return Fleet(job_ids, profiles,
                 {job_ids[j]: int(fault[j]) for j in range(jobs) if fault[j] >= 0},
                 hosts, d)


def encode_pool(fleet: Fleet, config: dict) -> list:
    """Per job, the wire packets of its pool windows (window number 0),
    labelled and encoded by the program's client side."""
    from repro.core import WindowAggregator
    from repro.core.contract import StageSchema
    from repro.telemetry.packets import encode_packet, from_diagnosis

    ranks, n = config["ranks"], config["window_steps"]
    out = []
    for j, job_id in enumerate(fleet.job_ids):
        sync = SYNC_PROFILES[fleet.profiles[j]]
        agg = WindowAggregator(StageSchema(STAGES, world_size=ranks), window_steps=n)
        packets = []
        block = fleet.durations[j]
        for t in range(block.shape[0]):
            report = agg.add_step(block[t], block[t].sum(-1))
            if report is None:
                continue
            pkt = from_diagnosis(
                report.diagnosis, STAGES, report.steps, ranks, 0,
                window=report.durations, sync_stages=sync, first_step=0,
                hosts=fleet.hosts[j],
            )
            packets.append(encode_packet(pkt, compress=config["compress"], wire="sfp2"))
        out.append(packets)
    return out


_MARK_W, _MARK_F = 987654321, 987654323


def template(wire: bytes) -> tuple:
    """Split an SFP2 packet around its window number and first step, so
    that `renumber` can rewrite both without parsing the header again."""
    magic, version, flags, hlen = struct.unpack_from("<4sBBI", wire, 0)
    head = json.loads(wire[10:10 + hlen])
    head["window_index"], head["first_step"] = _MARK_W, _MARK_F
    text = json.dumps(head).encode()
    a, rest = text.split(b"%d" % _MARK_W)
    b, c = rest.split(b"%d" % _MARK_F)
    return struct.pack("<4sBB", magic, version, flags), (a, b, c), wire[10 + hlen:]


def renumber(tpl: tuple, window_index: int, first_step: int) -> bytes:
    """The packet of `template` with another window number and first step."""
    fixed, (a, b, c), tail = tpl
    head = b"".join((a, b"%d" % window_index, b, b"%d" % first_step, c))
    return b"".join((fixed, struct.pack("<I", len(head)), head, tail))


@dataclasses.dataclass
class Schedule:
    """Every packet of a run in due order: due time (s from the stream's
    start), job index, window number, wire bytes."""

    period_s: float
    due: np.ndarray
    job: np.ndarray
    window: np.ndarray
    wire: list


def schedule(fleet: Fleet, pool: list, config: dict, traffic: dict, seed: int,
             horizon_s: float) -> Schedule:
    rng = np.random.default_rng([int(seed) % 2**64, 2])
    jobs, n = config["jobs"], config["window_steps"]
    period = jobs / traffic["rate_windows_per_s"]
    phase = rng.uniform(0.0, period, size=jobs)
    per_job = int(np.ceil(horizon_s / period)) + 1
    due = (phase[:, None] + period * np.arange(per_job)[None, :]).ravel()
    job = np.repeat(np.arange(jobs), per_job)
    win = np.tile(np.arange(per_job), jobs)
    order = np.argsort(due, kind="stable")
    due, job, win = due[order], job[order], win[order]
    keep = due < horizon_s
    due, job, win = due[keep], job[keep], win[keep]
    k = len(pool[0])
    tpl = [[template(w) for w in packets] for packets in pool]
    wire = [renumber(tpl[j][w % k], int(w), int(w) * n) for j, w in zip(job, win)]
    return Schedule(period, due, job, win, wire)
