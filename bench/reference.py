"""Plain reference of what a SEARS store must hold and return.

Written from the paper's definitions and the configuration file alone; it
imports nothing of the program.  Per file: gear content-defined chunking
(32-byte window, boundary where the hash's top ``log2(avg)`` bits are
zero, greedy min/max selection), SHA-1 chunk ids, and systematic
Reed-Solomon over GF(2^8) (polynomial 0x11D, generator ``[I; P]`` with
the Cauchy block ``P[i, j] = 1 / (x_i + y_j)``, ``x = k..n-1``,
``y = 0..k-1``; a chunk is zero-padded to ``k`` rows of
``ceil(len / k)`` bytes).  Placement follows the binding rule the
configuration names: ULB binds each user, in order of first write, to the
next cluster round-robin, and dedups within that cluster; CLB puts each
new chunk on the cluster with the most free bytes (the first such) and
dedups across the pool.

The checks compare the store after a run against this: every file's
chunk lengths, chunk ids and clusters, the store's totals (logical bytes,
files, unique chunk copies, piece bytes, index bytes), the n pieces of
every chunk copy a seeded sample of files added, and the bytes of every
get.  Each is a count of differences, and every limit is 0.
"""

from __future__ import annotations

import bisect
import hashlib
import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

from bench import traffic

# ------------------------------------------------------------ gear CDC ----
GEAR_SEED = 0x5EA125  # the chunker's published table seed
GEAR = np.random.RandomState(GEAR_SEED).randint(
    0, 2**32, size=256, dtype=np.uint64).astype(np.uint32)
WINDOW = 32
_TILE = 1 << 18


def _tile_hashes(data: np.ndarray, s0: int, e: int) -> np.ndarray:
    """h[t] = sum_{j<32} 2^j * GEAR[data[t-j]] for t in [s0, e), zero
    history before the file; built by doubling the window 1, 2, ..., 32."""
    lo = max(0, s0 - (WINDOW - 1))
    a = np.take(GEAR, data[lo:e])
    s = 1
    while s < WINDOW:
        a[s:] += a[:-s] << np.uint32(s)
        s *= 2
    return a[s0 - lo:]


def chunk_lengths(data: bytes, cmin: int, cavg: int, cmax: int
                  ) -> list[int]:
    """Chunk lengths of one file under gear CDC."""
    arr = np.frombuffer(data, dtype=np.uint8)
    n = arr.shape[0]
    if n == 0:
        return []
    bits = int(np.log2(cavg))
    mask = np.uint32(((1 << bits) - 1) << (32 - bits))
    cand = np.concatenate([
        np.flatnonzero((_tile_hashes(arr, s0, min(n, s0 + _TILE)) & mask)
                       == 0) + s0 + 1  # cut after byte t
        for s0 in range(0, n, _TILE)]).tolist()
    lengths, start = [], 0
    while start < n:
        if n - start <= cmin:
            cut = n
        else:
            end = min(start + cmax, n)
            i = bisect.bisect_left(cand, start + cmin)
            cut = cand[i] if i < len(cand) and cand[i] <= end else end
        lengths.append(cut - start)
        start = cut
    return lengths


# ------------------------------------------------------ GF(2^8) and RS ----
def _gf_tables() -> np.ndarray:
    exp = [0] * 510
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    mul = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            mul[a, b] = exp[log[a] + log[b]]
    return mul


MUL = _gf_tables()


def _inv(a: int) -> int:
    return int(np.flatnonzero(MUL[a] == 1)[0])


def parity_matrix(n: int, k: int) -> list[list[int]]:
    return [[_inv((k + i) ^ j) for j in range(k)] for i in range(n - k)]


def encode(chunk: bytes, n: int, k: int, parity: list[list[int]]
           ) -> list[bytes]:
    """The n pieces of one chunk: k data rows, then n-k parity rows."""
    L = max(1, -(-len(chunk) // k))
    rows = np.zeros(k * L, dtype=np.uint8)
    rows[:len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
    rows = rows.reshape(k, L)
    out = [rows[j].tobytes() for j in range(k)]
    for coeffs in parity:
        p = np.zeros(L, dtype=np.uint8)
        for j, c in enumerate(coeffs):
            p ^= MUL[c][rows[j]]
        out.append(p.tobytes())
    return out


# ------------------------------------------------------------- checks -----
def _meta(store, user: str, filename: str):
    try:
        return store.switching[user].get_meta(filename)
    except KeyError:
        return None


class _Expected:
    """The store a sound program leaves: placement, copies and totals."""

    def __init__(self, config: dict) -> None:
        sc = config["storage_class"]
        self.n, self.k = int(sc["n"]), int(sc["k"])
        self.ulb = sc["binding"] == "ulb"
        self.n_clusters = int(config["num_clusters"])
        self.bound: dict[str, int] = {}  # ULB: user -> cluster
        self.home: dict[bytes, int] = {}  # CLB: chunk id -> cluster
        self.free = [self.n * int(config["node_capacity"])] * self.n_clusters
        self.copies: dict[tuple[bytes, int], int] = {}  # -> chunk length
        self.logical = self.files = self.meta_bytes = 0

    def piece_len(self, length: int) -> int:
        return max(1, -(-length // self.k))

    def put(self, user: str, lengths: list[int], ids: list[bytes]):
        """Place one file; returns each chunk's cluster and the indices
        of the chunks that are new copies."""
        if self.ulb and user not in self.bound:
            self.bound[user] = len(self.bound) % self.n_clusters
        where, fresh = [], []
        for i, (cid, ln) in enumerate(zip(ids, lengths)):
            if self.ulb:
                cl = self.bound[user]
            elif cid in self.home:
                cl = self.home[cid]
            else:
                cl = max(range(self.n_clusters), key=self.free.__getitem__)
                self.free[cl] -= self.n * self.piece_len(ln)
                self.home[cid] = cl
            where.append(cl)
            if (cid, cl) not in self.copies:
                self.copies[(cid, cl)] = ln
                fresh.append(i)
        self.logical += sum(lengths)
        self.files += 1
        self.meta_bytes += 24 * len(ids) + 8
        return where, fresh

    def stats(self) -> dict[str, int]:
        return dict(logical_bytes=self.logical, n_files=self.files,
                    n_unique_chunks=len(self.copies),
                    piece_bytes=sum(self.n * self.piece_len(ln)
                                    for ln in self.copies.values()),
                    index_bytes=32 * len(self.copies) + self.meta_bytes)


def _digest(job) -> list[tuple[list[int], list[bytes]]]:
    """Chunk lengths and SHA-1 ids of some files (runs in a worker
    process, which makes the files' bytes again from the seed)."""
    seed, config, traffic_params, keys = job
    sc = config["storage_class"]
    src = traffic.make_source(seed, config, traffic_params)
    out = []
    for data in src.contents(keys):
        lengths = chunk_lengths(data, int(sc["chunk_min"]),
                                int(sc["chunk_avg"]), int(sc["chunk_max"]))
        view, ids, off = memoryview(data), [], 0
        for ln in lengths:
            ids.append(hashlib.sha1(view[off:off + ln]).digest())
            off += ln
        out.append((lengths, ids))
    return out


def check_puts(store, puts, seed: int, config: dict, traffic_params: dict,
               check_fraction: float) -> dict[str, int]:
    """Compare a store with the reference replay of its acknowledged puts.

    ``puts`` are every put the run made, in submission order.  Worker
    processes (spawned, so they hold no chip) make each client's files
    again from the seed and chunk and hash them; placement and the
    comparisons then run in order here.  For a seeded ``check_fraction``
    of the files (and the first), every chunk copy the file added is
    RS-encoded here and compared piece by piece with the nodes.
    """
    want = _Expected(config)
    n, k = want.n, want.k
    parity = parity_matrix(n, k)
    ok = [p for p in puts if p.ok]
    out = dict(puts_failed=len(puts) - len(ok), files_missing=0,
               spans_wrong=0, ids_wrong=0, placement_wrong=0,
               pieces_wrong=0, pieces_checked=0, stats_wrong=0)
    by_client: dict = {}
    for i, p in enumerate(ok):
        by_client.setdefault(p.key[0], []).append(i)
    digests: list = [None] * len(ok)
    workers = max(1, min(len(by_client), 12, os.cpu_count() or 1))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
        jobs = {c: ex.submit(_digest, (seed, config, traffic_params,
                                       [ok[i].key for i in idxs]))
                for c, idxs in by_client.items()}
        for c, job in jobs.items():
            for i, d in zip(by_client[c], job.result()):
                digests[i] = d
    check = np.random.default_rng([int(seed), 0xC4EC]).random(len(ok)) \
        < check_fraction
    check[:1] = True
    source = traffic.make_source(seed, config, traffic_params)
    for i, (rec, (lengths, ids)) in enumerate(zip(ok, digests)):
        where, fresh = want.put(rec.user, lengths, ids)
        meta = _meta(store, rec.user, rec.filename)
        if meta is None:
            out["files_missing"] += 1
            continue
        out["spans_wrong"] += list(meta.lengths) != lengths
        out["ids_wrong"] += [e[0] for e in meta.entries] != ids
        out["placement_wrong"] += [e[1] for e in meta.entries] != where
        if not check[i]:
            continue
        data = source.content(*rec.key)
        offsets = np.concatenate([[0], np.cumsum(lengths)]).tolist()
        for j in fresh:
            pieces = encode(data[offsets[j]:offsets[j + 1]], n, k, parity)
            got = store.clusters[where[j]].read_pieces(ids[j], n)
            out["pieces_checked"] += n
            out["pieces_wrong"] += sum(got.get(r) != pieces[r]
                                       for r in range(n))
    stats = store.stats()
    out["stats_wrong"] = sum(getattr(stats, f) != v
                             for f, v in want.stats().items())
    return out


def check_gets(window, source, files_keys: list[tuple]) -> dict[str, int]:
    """Every get of the window against the bytes that were put."""
    want: dict[int, bytes] = {}
    out = dict(gets_failed=0, gets_wrong=0)
    for idx, _, _, ok, data in window.gets:
        if not ok:
            out["gets_failed"] += 1
            continue
        if idx not in want:
            want[idx] = source.content(*files_keys[idx])
        out["gets_wrong"] += data != want[idx]
    return out
