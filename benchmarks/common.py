"""Shared paper-figure machinery: store builders, workload ingestion, replay."""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.classes import StorageClass
from repro.core.latency import LatencyParams, calibrate
from repro.core.radmad import RADMADStore
from repro.core.store import SEARSStore
from repro.core.workload import WorkloadConfig, generate_events, request_trace

# calibrated once against the paper's anchors (3 MB: 7 s single-stream,
# 2.5 s ULB(10,5)) and shared by every latency benchmark
_CAL: LatencyParams | None = None


def calibrated_params() -> LatencyParams:
    global _CAL
    if _CAL is None:
        _CAL = calibrate()
    return _CAL


def make_store(scheme: str, n: int = 10, k: int = 5, clusters: int = 20,
               node_capacity: int = 2 << 30, seed: int = 0,
               engine: str = "numpy"):
    lat = calibrated_params()
    if scheme == "radmad":
        # paper: 8 MB containers at full scale; scaled with the dataset
        return RADMADStore(n=n, k=k, num_clusters=clusters,
                           node_capacity=node_capacity,
                           container_size=512 << 10, latency=lat, seed=seed)
    cls = StorageClass(name="default", n=n, k=k, binding=scheme)
    # sanitize=False even under SEARS_SANITIZE=1: the figures run many
    # stores over the process-global LAUNCHES counters, outside the
    # sanitizer's single-store launch model
    return SEARSStore(classes=[cls], num_clusters=clusters,
                      node_capacity=node_capacity, sanitize=False,
                      latency=lat, seed=seed, engine=engine)


@dataclasses.dataclass
class IngestResult:
    store: object
    events: list
    day_marks: dict[int, float]  # day -> dedup ratio snapshot


def ingest(store, cfg: WorkloadConfig, snapshot_days=(5, 10, 15, 21),
           keep_events: bool = True) -> IngestResult:
    marks: dict[int, float] = {}
    events = []
    last_day = -1
    for ev in generate_events(cfg):
        if ev.day != last_day and last_day + 1 in snapshot_days:
            marks[last_day + 1] = store.stats().dedup_ratio
        last_day = ev.day
        ts = ev.day * 86400.0 + ev.hour * 3600.0
        store.put_file(ev.user, ev.filename, ev.data, timestamp=ts)
        if keep_events:
            events.append(ev)
    if last_day + 1 in snapshot_days:
        marks[last_day + 1] = store.stats().dedup_ratio
    if hasattr(store, "flush"):
        store.flush()
    return IngestResult(store=store, events=events, day_marks=marks)


def cluster_demand(store, requests: list[tuple], window_s: float = 3600.0,
                   amplification: float = 60_000.0) -> dict[int, float]:
    """Per-cluster utilisation rho from a set of concurrent requests.

    ``amplification`` rescales the 1/20000-scale trace volume back to the
    paper's full-scale byte demand (DESIGN.md S8).
    """
    demand: dict[int, float] = {}
    for user, filename in requests:
        try:
            if isinstance(store, RADMADStore):
                meta = store.files[(user, filename)]
                for cid, _ in meta.entries:
                    loc = store._chunks[cid]
                    if loc.container >= 0:
                        cl = store._container_cluster[loc.container]
                        demand[cl] = demand.get(cl, 0.0) + loc.length
            else:
                meta = store.switching[user].get_meta(filename)
                seen = set()
                for (cid, cl), ln in zip(meta.entries, meta.lengths):
                    if cid in seen:
                        continue
                    seen.add(cid)
                    demand[cl] = demand.get(cl, 0.0) + ln
        except KeyError:
            continue
    lat = calibrated_params()
    capacity = 10 * lat.conn_bw  # n node uplinks per cluster
    return {cl: min(0.95, amplification * b / window_s / capacity)
            for cl, b in demand.items()}


def replay_trace(store, cfg: WorkloadConfig, events,
                 amplification: float = 60_000.0):
    """Replay the diurnal retrieval trace; returns per-hour mean times."""
    trace = request_trace(cfg, events)
    by_hour: dict[int, list] = {h: [] for h in range(24)}
    times: dict[int, list[float]] = {h: [] for h in range(24)}
    for day, hour, user, filename in trace:
        by_hour[hour].append((day, user, filename))
    for hour, reqs in by_hour.items():
        if not reqs:
            continue
        rho = cluster_demand(store, [(u, f) for _, u, f in reqs],
                             amplification=amplification)
        rho_fn = lambda cl: rho.get(cl, 0.0)  # noqa: E731
        for _, user, filename in reqs:
            try:
                _, st = store.get_file(user, filename, rho_fn=rho_fn)
            except KeyError:
                continue
            times[hour].append(st.time_s)
    return {h: (float(np.mean(v)) if v else float("nan"))
            for h, v in times.items()}, trace
