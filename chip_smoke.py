"""Bring-up smoke of the SEARS served path on one TPU chip.

    python chip_smoke.py

Drives ``BatchScheduler`` over ``SEARSStore`` with the device engine
through the calls a client makes (``submit_put``/``submit_get``/
``flush``) over seeded mixed real-time/archival traffic: about 240 MiB of
3 MiB files from 16 users, a fifth of them small edits of earlier files.
Phases: put everything; read it back healthy; kill n-k nodes in every
cluster and read it back degraded (the non-systematic GF decode); replace
the nodes and repair; read it back again.

Checks: every request succeeds and every get returns exactly the bytes
that were put; the same submits on an ``engine="numpy"`` store (host
hashlib and per-chunk RS, the reference) leave identical pieces on every
node and identical ``StoreStats``; the put phase on an ``engine="fused"``
store leaves the same pieces; the gear, SHA-1, GF and fused launch
counters all moved, and no chunk was hashed on the host.

Exits non-zero without a result line when JAX finds no TPU.  Otherwise
the last line of stdout is ``{"ok": true, "device": {...}}``.  The phase
wall times it prints come from one cold run, compilation included: they
are not a benchmark.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import SEARSStore, hashing  # noqa: E402
from repro.core.classes import StorageClass  # noqa: E402
from repro.core.workload import (MixedClassConfig,  # noqa: E402
                                 mixed_class_trace)
from repro.kernels.launches import LAUNCHES, TRACES  # noqa: E402


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Traffic and store shape.  The defaults are the chip run."""

    n_users: int = 16
    hot_files: int = 3  # real-time class: fresh files per user
    cold_files: int = 2  # archival class: a file, then an edit of it
    file_kb: int = 3 << 10  # the paper's headline file size
    flush_bytes: int = 64 << 20  # put window: one 64 MiB gear stream
    num_clusters: int = 20
    seed: int = 12


class SmokeError(AssertionError):
    """A smoke check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def traffic(cfg: SmokeConfig) -> list[tuple[str, list[tuple[str, bytes]],
                                            str]]:
    """Seeded (user, files, storage class) put requests."""
    return mixed_class_trace(MixedClassConfig(
        n_users=cfg.n_users, hot_files_per_user=cfg.hot_files,
        hot_kb=cfg.file_kb, cold_files_per_user=cfg.cold_files,
        cold_kb=cfg.file_kb, seed=cfg.seed))


def piece_digests(store) -> dict[tuple[int, int], str]:
    """SHA-256 over every (chunk, piece) held, per (cluster, node)."""
    out = {}
    for c in store.clusters:
        for node in c.nodes:
            h = hashlib.sha256()
            for key in sorted(node._pieces):
                h.update(key[0] + key[1].to_bytes(2, "big"))
                h.update(node._pieces[key])
            out[(c.cluster_id, node.node_id)] = h.hexdigest()
    return out


def _dead_nodes(cluster) -> list[int]:
    """n-k nodes, every other one from 0: all systematic for both presets,
    so a degraded read must decode through parity pieces."""
    return list(range(0, 2 * (cluster.n - cluster.k), 2))


def drive(engine: str, trace, cfg: SmokeConfig, put_only: bool = False,
          log=print) -> dict:
    """Run the phases on a fresh store; check every result's bytes."""
    classes = [StorageClass.realtime(), StorageClass.archival()]
    store = SEARSStore(classes=classes, num_clusters=cfg.num_clusters,
                       engine=engine)
    eng = store.engine
    # the only host hash branch is a custom id function, and a chunk over
    # the device cap raises: together these mean every chunk is hashed on
    # the device
    check(getattr(eng, "hash_fn", hashing.chunk_id) is hashing.chunk_id,
          f"{engine}: custom hash_fn would hash on the host")
    check(getattr(eng, "max_hash_len", 1 << 62)
          >= max(c.chunk_max for c in classes),
          f"{engine}: SHA-1 cap below the largest chunk_max")
    sched = store.scheduler(flush_bytes=cfg.flush_bytes)
    want = {(user, fn): data for user, files, _ in trace
            for fn, data in files}
    out: dict = {"engine": engine, "impl": getattr(eng, "impl", None),
                 "seconds": {}}
    launches0 = LAUNCHES.snapshot()

    def timed(name, fn):
        t0 = time.perf_counter()
        res = fn()
        out["seconds"][name] = time.perf_counter() - t0
        return res

    def put_all():
        futs = [sched.submit_put(user, files, storage_class=cls)
                for user, files, cls in trace]
        sched.flush()
        for f in futs:
            check(f.ok, f"{engine}: put {f.user} failed: {f.error!r}")
        return [f.result() for f in futs]

    def get_all(label):
        users = sorted({user for user, _, _ in trace})
        futs = {u: sched.submit_get(u, [fn for (v, fn) in want if v == u])
                for u in users}
        sched.flush()
        n = 0
        for u, f in futs.items():
            check(f.ok, f"{engine}: {label} get {u} failed: {f.error!r}")
            names = [fn for (v, fn) in want if v == u]
            for fn, (data, _) in zip(names, f.result()):
                check(data == want[(u, fn)],
                      f"{engine}: {label} get {u}/{fn}: bytes differ")
                n += 1
        check(n == len(want), f"{engine}: {label} read {n}/{len(want)}")

    out["put"] = timed("put", put_all)
    out["stats_put"] = store.stats()
    out["pieces_put"] = piece_digests(store)
    if not put_only:
        timed("get_healthy", lambda: get_all("healthy"))
        for c in store.clusters:
            c.kill_nodes(_dead_nodes(c))
        timed("get_degraded", lambda: get_all("degraded"))
        for c in store.clusters:
            c.replace_nodes(_dead_nodes(c))
        report = timed("repair", store.repair_all)
        check(report.balanced, f"{engine}: repair report unbalanced")
        check(not report.unrecoverable and not report.failed,
              f"{engine}: repair left {len(report.unrecoverable)} "
              f"unrecoverable, {len(report.failed)} failed")
        check(report.pieces_rebuilt > 0, f"{engine}: repair rebuilt nothing")
        out["pieces_rebuilt"] = report.pieces_rebuilt
        timed("get_repaired", lambda: get_all("repaired"))
        out["stats_end"] = store.stats()
        out["pieces_end"] = piece_digests(store)
    out["launches"] = LAUNCHES.delta(launches0)
    out["logical_bytes"] = store.logical_bytes
    log(f"{engine} engine (impl={out['impl']}): "
        + ", ".join(f"{k} {v:.3f} s" for k, v in out["seconds"].items())
        + f"; launches {out['launches']}")
    return out


def run(cfg: SmokeConfig = SmokeConfig(), log=print) -> dict:
    """Device engine through every phase, checked against the references.

    Returns the device store's results; raises :class:`SmokeError` on the
    first check that fails.
    """
    trace = traffic(cfg)
    dev = drive("kernel", trace, cfg, log=log)
    ref = drive("numpy", trace, cfg, log=log)
    check(dev["put"] == ref["put"], "put results differ from numpy")
    for when in ("put", "end"):
        check(dev[f"stats_{when}"] == ref[f"stats_{when}"],
              f"StoreStats after {when} differ from numpy")
        check(dev[f"pieces_{when}"] == ref[f"pieces_{when}"],
              f"node pieces after {when} differ from numpy")
    check(dev["pieces_rebuilt"] == ref["pieces_rebuilt"],
          "repair rebuilt a different piece count than numpy")
    fused = drive("fused", trace, cfg, put_only=True, log=log)
    check(fused["pieces_put"] == dev["pieces_put"],
          "fused engine's pieces differ from the staged engine's")
    check(fused["stats_put"] == dev["stats_put"],
          "fused engine's StoreStats differ")
    d, f = dev["launches"], fused["launches"]
    check(d.gear > 0 and d.sha1 > 0 and d.gf > 0 and f.fused > 0,
          f"a data-plane family never launched: kernel {d}, fused {f}")
    stats = dev["stats_end"]
    log(f"logical {dev['logical_bytes']} bytes in {stats.n_files} files, "
        f"{stats.n_unique_chunks} unique chunks, dedup ratio "
        f"{stats.dedup_ratio:.4f}; pieces rebuilt {dev['pieces_rebuilt']}")
    log(f"compiles per family (TRACES): {TRACES}")
    return dev


def main() -> int:
    from repro.kernels.ops import use_compile_cache
    use_compile_cache()
    import jax
    devs = jax.devices()
    dev0 = devs[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev0.platform}",
              file=sys.stderr)
        return 1
    print(f"device: {dev0.device_kind} x{len(devs)}; phase times are one "
          "cold run including compilation, not a benchmark")
    try:
        res = run()
        check(res["impl"] == "kernel",
              f"device engine resolved to impl={res['impl']!r}, not the "
              "Pallas kernels")
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
