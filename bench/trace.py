"""From a profiler trace to device busy time, kernel time and idle gaps.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: device operations (HLO instruction, XLA module, start,
duration in ns) from every device plane's ``XLA Ops`` line, and the
benchmark's own host spans (names starting ``bench.``).  ``reduce``
works on those lists alone, so a small recorded trace can check it
without a chip.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


def _op(name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names (``%x.1 = ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def collect(trace_dir: str) -> dict:
    """Device ops and ``bench.`` host spans of the trace under a dir.

    A device op is ``[plane, instruction, module, start_ns, dur_ns]``: the
    HLO instruction from the ``XLA Ops`` line (a Pallas kernel is the
    custom call named after its jitted wrapper, ``_gf_matmul_padded.1``)
    and the module of the ``XLA Modules`` line that holds it.
    """
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device, host = [], []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            lines = {line.name: line for line in plane.lines}
            if plane.name.startswith("/device:") and DEVICE_LINE in lines:
                mod_line = lines.get(MODULE_LINE)
                mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                               e.name.split("(", 1)[0])
                              for e in (mod_line.events if mod_line else ()))
                starts = [m[0] for m in mods]
                for e in lines[DEVICE_LINE].events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    module = (mods[i][2] if i >= 0 and
                              e.start_ns < mods[i][1] else "")
                    device.append([plane.name, _op(e.name), module,
                                   float(e.start_ns), float(e.duration_ns)])
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            host.append([e.name, float(e.start_ns),
                                         float(e.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(events: dict, window: str = "bench.window",
           exclude: tuple[str, ...] = ("bench.generate",)) -> dict:
    """Busy and window seconds, per-module device seconds and a breakdown.

    Everything is clipped to the host span named ``window``.  Busy time
    is the union of each device's op intervals, averaged over the devices
    that ran any op.  Each idle gap is charged to the innermost ``bench.``
    span that covers its midpoint (``host`` where none does).  Spans named
    in ``exclude`` (the load generator making data) are taken out of the
    window, and their gaps out of the idle time.
    """
    spans = [s for s in events["host"] if s[0] == window]
    if not spans:
        raise ValueError(f"trace holds no {window!r} span")
    w0 = min(s[1] for s in spans)
    w1 = max(s[1] + s[2] for s in spans)
    excl = [(max(s[1], w0), min(s[1] + s[2], w1)) for s in events["host"]
            if s[0] in exclude and s[1] < w1 and s[1] + s[2] > w0]
    excluded = sum(e - s for s, e in _union(excl))
    per_device: dict[str, list] = {}
    module_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    for plane, name, module, start, dur in events["device"]:
        s, e = max(start, w0), min(start + dur, w1)
        if e <= s:
            continue
        per_device.setdefault(plane, []).append((s, e))
        module_s[module] = module_s.get(module, 0.0) + (e - s) * 1e-9
        key = f"{module}:{name}" if module else name
        op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
    busy, gaps = [], {}
    inner = sorted((s for s in events["host"] if s[0] != window),
                   key=lambda s: s[2])
    for plane, ivs in per_device.items():
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        covered = _union(ivs + excl)
        edges = [w0] + [x for iv in covered for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            name = next((h[0] for h in inner
                         if h[1] <= mid <= h[1] + h[2]), "host")
            if name not in exclude:
                gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9
    n_dev = max(1, len(per_device))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((k, v / n_dev) for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0 - excluded) * 1e-9,
            "busy_s": sum(busy) / n_dev,
            "module_s": module_s,
            "op_s": op_s,
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in idle]}}


def kernel_seconds(reduction: dict, ops) -> float:
    """Device seconds of the instructions named ``ops`` (any ``.N``)."""
    return sum(s for key, s in reduction["op_s"].items()
               if re.sub(r"\.\d+$", "", key.rsplit(":", 1)[-1]) in ops)
