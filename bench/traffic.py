"""Seeded traffic for the benchmark cells: data sources and the loops that
drive them.

Everything a run sends is made here from ``--seed``; the program receives
only the generated requests.  Two data sources, both copied in shape from
the program's own workload generators so that later changes there cannot
move the yardstick:

* ``mixed_files``: fresh files of a fixed size, each a mix of private
  random blocks and blocks drawn from a small shared pool (the personal-
  data redundancy of the paper's trace: ``workload._mixed_bytes`` over a
  ``_BlockPool``, in ``mixed_class_trace``'s shape).  Client ``c``'s
  ``j``-th file depends only on ``(seed, c, j)``.
* ``backup_images``: one image per user per night; night 0 is random,
  each later night rewrites ``churn`` of the previous image in
  ``spot_bytes`` spots (``generate_events``' backup images).

Three loops, chosen by the traffic file's ``kind``:

* ``put_rounds``: a closed loop.  Each round is one file per client (or
  one night of images); a round is submitted when the previous one is
  acknowledged, and the scheduler's ``flush_bytes`` closes windows.
* ``open_get``: an open loop of single-file gets at a fixed rate over a
  prefilled store, optionally with nodes killed first.  The send times
  (exponential-quantile gaps in an order the traffic file fixes) are the
  same for every seed; seeds change the stored bytes and which files are
  read, not how much work arrives or when.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable

import numpy as np

# ---------------------------------------------------------------- sources --


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng([int(k) for k in key])


_POOL, _FILE, _IMAGE, _ORDER, PICK = 1, 2, 3, 4, 5  # seed-stream tags


class MixedFiles:
    """Fresh fixed-size files: private random blocks + shared-pool blocks."""

    def __init__(self, seed: int, data: dict, clients: int) -> None:
        self.seed = seed
        self.clients = clients
        self.file_bytes = int(data["file_bytes"])
        self.block = int(data["block_bytes"])
        self.shared_fraction = float(data["shared_fraction"])
        self.pool = _rng(seed, _POOL).integers(
            0, 256, (int(data["pool_blocks"]), self.block), dtype=np.uint8)

    def user(self, client: int) -> str:
        return f"c{client:02d}"

    def name(self, client: int, j: int) -> str:
        return f"f{j:05d}"

    def content(self, client: int, j: int) -> bytes:
        r = _rng(self.seed, _FILE, client, j)
        nblk = -(-self.file_bytes // self.block)
        shared = r.random(nblk) < self.shared_fraction
        pick = r.integers(self.pool.shape[0], size=nblk)
        out = r.integers(0, 256, (nblk, self.block), dtype=np.uint8)
        out[shared] = self.pool[pick[shared]]
        return out.reshape(-1)[:self.file_bytes].tobytes()

    def round(self, r: int) -> list[tuple[str, str, tuple]]:
        """Round ``r``: each client's ``r``-th file, as (user, name, key)."""
        return [(self.user(c), self.name(c, r), (c, r))
                for c in range(self.clients)]

    def contents(self, keys):
        """Content of every key, in order (keys of one or many rounds)."""
        for key in keys:
            yield self.content(*key)


class BackupImages:
    """Nightly backup images with day-over-day churn in fixed-size spots."""

    def __init__(self, seed: int, data: dict, clients: int) -> None:
        self.seed = seed
        self.users = int(data["users"])
        self.image_bytes = int(data["image_bytes"])
        self.spot = int(data["spot_bytes"])
        self.spots = max(1, int(self.image_bytes * float(data["churn"]))
                         // self.spot)
        self._night: dict[int, int] = {}  # user -> night of _image[user]
        self._image: dict[int, np.ndarray] = {}

    def user(self, u: int) -> str:
        return f"u{u:02d}"

    def name(self, u: int, night: int) -> str:
        return f"image.night{night:03d}"

    def _advance(self, u: int, night: int) -> np.ndarray:
        if self._night.get(u, night + 1) > night:  # start over from night 0
            self._image[u] = _rng(self.seed, _IMAGE, u, 0).integers(
                0, 256, self.image_bytes, dtype=np.uint8)
            self._night[u] = 0
        img = self._image[u]
        while self._night[u] < night:
            d = self._night[u] + 1
            r = _rng(self.seed, _IMAGE, u, d)
            offs = r.integers(0, self.image_bytes - self.spot, self.spots)
            spots = r.integers(0, 256, (self.spots, self.spot), dtype=np.uint8)
            for off, spot in zip(offs, spots):
                img[off:off + self.spot] = spot
            self._night[u] = d
        return img

    def content(self, u: int, night: int) -> bytes:
        return self._advance(u, night).tobytes()

    def round(self, night: int) -> list[tuple[str, str, tuple]]:
        return [(self.user(u), self.name(u, night), (u, night))
                for u in range(self.users)]

    def contents(self, keys):
        for key in keys:
            yield self.content(*key)


SOURCES = {"mixed_files": MixedFiles, "backup_images": BackupImages}


def make_source(seed: int, config: dict, traffic: dict):
    return SOURCES[config["data"]["source"]](
        seed, config["data"], int(traffic["clients"]))


# ------------------------------------------------------------------ loops --


@dataclasses.dataclass
class PutRecord:
    """One put request: who, which file, the source key of its bytes."""

    user: str
    filename: str
    key: tuple
    nbytes: int
    ok: bool = False


@dataclasses.dataclass
class Window:
    """What one measured window did (host clock, seconds)."""

    seconds: float = 0.0  # the window's length on the host clock
    generate_s: float = 0.0  # load-generator time, outside the window
    attempted: int = 0
    failed: int = 0
    put_bytes: int = 0  # logical bytes of acknowledged puts
    errors: list = dataclasses.field(default_factory=list)  # failed requests
    # open-loop gets: (file index, scheduled s, done s, ok, bytes or None)
    gets: list = dataclasses.field(default_factory=list)
    get_bytes: int = 0
    late_s: list = dataclasses.field(default_factory=list)


class Clock:
    """Window time that stops while the load generator makes data."""

    def __init__(self) -> None:
        self.paused = 0.0
        self._t0 = time.perf_counter()
        self._pause_at: float | None = None

    def now(self) -> float:
        return time.perf_counter() - self._t0 - self.paused

    def pause(self) -> None:
        self._pause_at = time.perf_counter()

    def resume(self) -> None:
        self.paused += time.perf_counter() - self._pause_at
        self._pause_at = None


def no_span(name: str):
    """The span of an untraced run: nothing."""
    return contextlib.nullcontext()


def put_rounds(sched, source, first_round: int, seconds: float | None,
               n_rounds: int | None = None, span=no_span,
               log: list | None = None) -> tuple[Window, int]:
    """Closed loop of put rounds; returns (window, next round).

    Runs ``n_rounds`` rounds, or, with ``seconds``, whole flushes until
    the window's clock passes ``seconds``: the check comes after each
    flush, so every request the window submitted is acknowledged inside
    it.  Content is made with the clock stopped.  ``log`` collects every
    request in submission order.
    """
    w = Window()
    clock = Clock()
    pending: list = []

    def settle() -> bool:
        """Record the acknowledged requests; True once time is up."""
        for rec, fut in pending:
            w.attempted += 1
            rec.ok = fut.ok
            if fut.ok:
                w.put_bytes += rec.nbytes
            else:
                w.failed += 1
                w.errors.append(repr(fut.error))
            if log is not None:
                log.append(rec)
        pending.clear()
        return seconds is not None and clock.now() >= seconds

    r = first_round
    done = False
    while not done and (n_rounds is None or r < first_round + n_rounds):
        reqs = source.round(r)
        r += 1
        clock.pause()
        with span("bench.generate"):
            datas = list(source.contents([key for _, _, key in reqs]))
        clock.resume()
        for (user, name, key), data in zip(reqs, datas):
            with span("bench.submit"):
                fut = sched.submit_put(user, [(name, data)])
            pending.append((PutRecord(user, name, key, len(data)), fut))
            if sched.pending == 0 and settle():  # the submit flushed
                done = True
                break
        del datas
        if not done and sched.pending:
            # every client waits on the open window: commit it
            with span("bench.commit"):
                sched.flush()
            done = settle()
    w.seconds = clock.now()
    w.generate_s = clock.paused
    return w, r


def arrivals(rate: float, seconds: float, order: int) -> np.ndarray:
    """Scheduled send times of an open loop: ``rate * seconds`` arrivals.

    The gaps are the exponential distribution's quantiles at the midpoints
    of ``n`` equal slices, scaled to span ``seconds`` exactly, in an order
    drawn from ``order``.  The traffic file fixes ``order``, so every run
    seed sends at the same times; the seed picks what is sent.
    """
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    order = _rng(order, _ORDER).permutation(n)
    return np.cumsum(gaps[order]) - gaps[order][0]


def open_gets(sched, files: list[tuple[str, str]], times: np.ndarray,
              picks: np.ndarray, span=no_span,
              sleep: Callable[[float], None] = time.sleep) -> Window:
    """Open loop: get ``files[picks[i]]`` at ``times[i]``; group commit.

    Whenever gets are pending the scheduler flushes them together; each
    get's latency runs from its scheduled send time to the end of the
    flush that returned its bytes.  Every scheduled get is answered, the
    last ones after the schedule ends.
    """
    w = Window()
    t0 = time.perf_counter()
    i, n = 0, len(times)
    while i < n:
        now = time.perf_counter() - t0
        if times[i] > now:
            with span("bench.wait"):
                sleep(times[i] - now)
            continue
        batch = []
        while i < n and times[i] <= time.perf_counter() - t0:
            user, name = files[picks[i]]
            w.late_s.append(time.perf_counter() - t0 - times[i])
            with span("bench.submit"):
                batch.append((i, sched.submit_get(user, [name])))
            i += 1
        with span("bench.commit"):
            sched.flush()
        done = time.perf_counter() - t0
        for j, fut in batch:
            w.attempted += 1
            if fut.ok:
                data = fut.request.result[0][0]
                w.get_bytes += len(data)
                w.gets.append((int(picks[j]), float(times[j]), done, True,
                               data))
            else:
                w.failed += 1
                w.errors.append(repr(fut.error))
                w.gets.append((int(picks[j]), float(times[j]), done, False,
                               None))
    w.seconds = time.perf_counter() - t0
    return w


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
