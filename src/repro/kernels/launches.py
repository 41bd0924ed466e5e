"""The program's counters, dependency-free: launches, spans, transfers.

Lives apart from ``ops`` so batching layers (``core.scheduler``,
benchmarks) can read the counters without importing jax and the Pallas
kernel modules — a NumpyEngine store never pays that import just to
snapshot counts that stay zero on its path.

Four families, each with ``snapshot()``/``delta()``:

* ``LAUNCHES``/``TRACES`` — data-plane dispatches and retraces;
* ``SPANS`` — host seconds and entries per ``sears.*`` step, filled by
  :func:`span`, which also marks the step on the ``jax.profiler``
  timeline (only once jax is loaded);
* ``TRANSFERS`` — bytes shipped host→device at each dispatch and
  device→host at each materialization (:func:`to_host`);
* ``SPECULATION`` — chunk bytes the fused ingest encoded before dedup,
  and how many of them an upload then kept.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np


@dataclasses.dataclass
class LaunchCounter:
    """Data-plane dispatch counts (one increment per device launch)."""

    gf: int = 0  # GF(256) matmul launches (encode + decode buckets)
    sha1: int = 0  # SHA-1 batch launches
    gear: int = 0  # gear CDC rolling-hash launches (chunking stream)
    fused: int = 0  # fused SHA-1+GF ingest launches (one per bucket)

    @property
    def total(self) -> int:
        return self.gf + self.sha1 + self.gear + self.fused

    def snapshot(self) -> "LaunchCounter":
        return dataclasses.replace(self)

    def delta(self, since: "LaunchCounter") -> "LaunchCounter":
        return LaunchCounter(gf=self.gf - since.gf,
                             sha1=self.sha1 - since.sha1,
                             gear=self.gear - since.gear,
                             fused=self.fused - since.fused)

    def reset(self) -> None:
        self.gf = self.sha1 = self.gear = self.fused = 0


LAUNCHES = LaunchCounter()

# Retrace counts: incremented *at trace time* inside the jitted data-plane
# entry points, so a counter that keeps growing across same-bucket calls
# is a jit-cache miss (the retrace bug the bucketed padding fixes).  One
# increment per (function, shape) compilation, not per call.
TRACES = LaunchCounter()


class SpanCounter:
    """Host seconds and entry counts per span name (see :func:`span`)."""

    def __init__(self, seconds: dict[str, float] | None = None,
                 count: dict[str, int] | None = None) -> None:
        self.seconds: dict[str, float] = dict(seconds or {})
        self.count: dict[str, int] = dict(count or {})

    def add(self, name: str, seconds: float) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        self.count[name] = self.count.get(name, 0) + 1

    def snapshot(self) -> "SpanCounter":
        return SpanCounter(self.seconds, self.count)

    def delta(self, since: "SpanCounter") -> "SpanCounter":
        return SpanCounter(
            {k: v - since.seconds.get(k, 0.0)
             for k, v in self.seconds.items()
             if self.count[k] != since.count.get(k, 0)},
            {k: v - since.count.get(k, 0) for k, v in self.count.items()
             if v != since.count.get(k, 0)})

    def reset(self) -> None:
        self.seconds.clear()
        self.count.clear()


SPANS = SpanCounter()


class span:
    """Time one program step into ``SPANS`` under ``name``.

    Opened once per window, shard group or kernel bucket, never per
    chunk, piece or node.  When jax is already imported the step is
    also a ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation``
    with ``step``), so a profiler trace shows it on the host timeline,
    on the same clock as the device ops; a store that never imported
    jax does not import it here.  Adds no device synchronization.
    ``seconds`` holds the step's time once it has closed.
    """

    __slots__ = ("name", "step", "seconds", "_note", "_t0")

    def __init__(self, name: str, step: int | None = None) -> None:
        self.name, self.step, self._note = name, step, None
        self.seconds = 0.0

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        prof = getattr(jax, "profiler", None)
        if prof is not None:
            self._note = (prof.TraceAnnotation(self.name)
                          if self.step is None else
                          prof.StepTraceAnnotation(self.name,
                                                   step_num=self.step))
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        SPANS.add(self.name, self.seconds)
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None


@dataclasses.dataclass
class TransferCounter:
    """Bytes copied between host and device by the data plane.

    ``h2d_bytes`` counts the host arrays each dispatch ships, padding
    included (arrays cached on the device, such as coding matrices, are
    shipped once and not counted); ``d2h_bytes`` the results copied back
    where the program materializes them.
    """

    h2d_bytes: int = 0
    d2h_bytes: int = 0

    def snapshot(self) -> "TransferCounter":
        return dataclasses.replace(self)

    def delta(self, since: "TransferCounter") -> "TransferCounter":
        return TransferCounter(h2d_bytes=self.h2d_bytes - since.h2d_bytes,
                               d2h_bytes=self.d2h_bytes - since.d2h_bytes)

    def reset(self) -> None:
        self.h2d_bytes = self.d2h_bytes = 0


TRANSFERS = TransferCounter()


@dataclasses.dataclass
class SpeculationCounter:
    """Chunk bytes RS-encoded before the dedup pass decided on them.

    A fused engine hashes and encodes every distinct ``(code, chunk)``
    job of a put window together (``encoded_bytes``); the store counts in
    ``kept_bytes`` the jobs whose pieces an upload task then took.  The
    rest, ``dropped_bytes``, were encoded for nothing: dedup hits on a
    stored copy, or chunks a later request in the window deleted.
    """

    encoded_bytes: int = 0
    kept_bytes: int = 0

    @property
    def dropped_bytes(self) -> int:
        return self.encoded_bytes - self.kept_bytes

    def snapshot(self) -> "SpeculationCounter":
        return dataclasses.replace(self)

    def delta(self, since: "SpeculationCounter") -> "SpeculationCounter":
        return SpeculationCounter(
            encoded_bytes=self.encoded_bytes - since.encoded_bytes,
            kept_bytes=self.kept_bytes - since.kept_bytes)

    def reset(self) -> None:
        self.encoded_bytes = self.kept_bytes = 0


SPECULATION = SpeculationCounter()


def shipped(*arrays) -> None:
    """Count the host arrays a dispatch copies to the device (an array
    already on the device costs nothing)."""
    TRANSFERS.h2d_bytes += sum(int(a.nbytes) for a in arrays
                               if isinstance(a, (np.ndarray, np.generic)))


def to_host(result) -> np.ndarray:
    """Wait for a device result and copy it to the host.

    The wait is the program's existing materialization point: it is
    timed as ``sears.engine.wait`` and its bytes counted as
    device→host.  A host array (a numpy ``apply_fn``) passes through.
    """
    if isinstance(result, np.ndarray):
        return result
    with span("sears.engine.wait"):
        out = np.asarray(result)
    TRANSFERS.d2h_bytes += out.nbytes
    return out


def reset_all() -> None:
    """Zero every counter family together.

    Resetting only one family skews any assertion that reads a launch
    delta against a trace count from an earlier phase (or vice versa),
    so benches and tests go through this instead of ``LAUNCHES.reset()``
    — the ``counter-family-reset`` lint rule enforces it.
    """
    LAUNCHES.reset()
    TRACES.reset()
    SPANS.reset()
    TRANSFERS.reset()
    SPECULATION.reset()


def snapshot_all() -> dict:
    """Point-in-time snapshot of every family, keyed 'launches'/'traces'/
    'spans'/'transfers'/'speculation'."""
    return {"launches": LAUNCHES.snapshot(), "traces": TRACES.snapshot(),
            "spans": SPANS.snapshot(), "transfers": TRANSFERS.snapshot(),
            "speculation": SPECULATION.snapshot()}


def delta_all(since: dict) -> dict:
    """Per-family deltas against a :func:`snapshot_all` result."""
    return {"launches": LAUNCHES.delta(since["launches"]),
            "traces": TRACES.delta(since["traces"]),
            "spans": SPANS.delta(since["spans"]),
            "transfers": TRANSFERS.delta(since["transfers"]),
            "speculation": SPECULATION.delta(since["speculation"])}
