"""One run of one benchmark cell: set-up, measured window, reference check.

Everything that belongs to one cell is found by name under ``bench/``:

* ``workloads/<cell>.json``: the configuration, the traffic and the chips;
* ``configs/<config>.json``: the deployment (storage class, clusters,
  scheduler, data set and the guarantees it gives);
* ``traffic/<traffic>.json``: the loop and its parameters;
* ``metrics/<metric>.py``: a ``read(ctx)`` that returns the metric, or
  ``None`` where the run has nothing for it to read;
* ``work/<kernel>.py``: the operations and bytes a kernel's result needs;
* ``peaks.json``: the chip's peaks by ``device_kind``.

Which metrics a run reports comes from ``BENCHMARK.json``: the cell's
end-to-end metrics without a trace, its per-layer metrics with one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import reference, trace, traffic
from bench.faults import FAULTS

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, wrong path, bad files)."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: Path = BENCH) -> Cell:
    wl = _json(bench_dir / "workloads" / f"{name}.json")
    return Cell(name=name, config_name=wl["config"],
                traffic_name=wl["traffic"], chips=int(wl["chips"]),
                config=_json(bench_dir / "configs" / f"{wl['config']}.json"),
                traffic=_json(bench_dir / "traffic"
                              / f"{wl['traffic']}.json"))


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(benchmark: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics this cell reports: per-layer when traced."""
    group = benchmark["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class Compiles:
    """Counts executables built or loaded, through JAX's own events:
    ``names`` holds every executable the process obtained, ``hits`` the
    ones that came from the persistent compile cache."""

    _instance = None

    def __init__(self) -> None:
        import jax.monitoring
        self.names: list[str] = []
        self.seconds: list[float] = []
        self.hits: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE:
            self.names.append(str(kwargs.get("fun_name", "?")))
            self.seconds.append(float(duration))

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT:
            self.hits.append(event)

    @classmethod
    def get(cls) -> "Compiles":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    @property
    def count(self) -> int:
        return len(self.names)


class Context:
    """What a metric reader may read about one run."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def roofline(self, kernel: str) -> float | None:
        """Percent of the kernel's device time that its required work
        needs at the chip's peaks (the larger of its two bounds)."""
        calls = self.instrument.calls.get(kernel) if self.instrument else None
        if not calls or self.reduction is None:
            return None
        mod = self.work[kernel]
        secs = trace.kernel_seconds(self.reduction, mod.TRACE_OPS)
        if secs <= 0:
            return None
        least = 0.0
        for call in calls:
            ops, nbytes = mod.work(call)
            t_ops = ops / self.peaks[mod.PEAK_OPS] if mod.PEAK_OPS else 0.0
            least += max(t_ops, nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / secs


def peaks_for(kind: str, bench_dir: Path = BENCH) -> dict:
    table = _json(bench_dir / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def build_store(config: dict):
    from repro.core import SEARSStore
    from repro.core.classes import StorageClass
    store = SEARSStore(classes=[StorageClass(**config["storage_class"])],
                       num_clusters=int(config["num_clusters"]),
                       node_capacity=int(config["node_capacity"]),
                       engine=config["engine"], shards=int(config["shards"]),
                       cache=bool(config["cache"]), sanitize=False)
    return store, store.scheduler(**config["scheduler"])


def _warm(compiles: "Compiles", step, least: int, most: int, log) -> int:
    """Run ``step`` until two passes in a row add no executable."""
    quiet = 0
    for i in range(most):
        before = compiles.count
        step(i)
        added = compiles.count - before
        log(f"warm pass {i}: {added} executables built or loaded")
        quiet = quiet + 1 if added == 0 else 0
        if quiet >= 2 and i + 1 >= least:
            return i + 1
    return most


class Run:
    """One cell set up for measuring: store, scheduler, data, warm-up.

    Set-up is everything up to the first window: JAX's start-up, the
    store, the prefill the traffic needs and the warm-up passes.
    """

    def __init__(self, cell: Cell, seed: int, traced: bool, *,
                 started: float, require_tpu: bool = True,
                 bench_dir: Path = BENCH,
                 log=lambda s: print(s, file=sys.stderr)) -> None:
        import jax
        self.jax = jax
        self.cell, self.seed, self.traced, self.log = cell, seed, traced, log
        devs = jax.devices()
        self.devices = devs
        dev = devs[0]
        log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
            f"count={len(devs)}")
        if require_tpu and dev.platform != "tpu":
            raise BenchError(f"needs a TPU, JAX found {dev.platform}")
        if len(devs) < cell.chips:
            raise BenchError(f"cell needs {cell.chips} chips, found "
                             f"{len(devs)}")
        from repro.kernels.ops import use_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache: {use_compile_cache()}")
        self.compiles = Compiles.get()
        self.bench_dir = bench_dir
        self.work = {p.stem: load_module(p)
                     for p in sorted((bench_dir / "work").glob("*.py"))}
        self.peaks = peaks_for(dev.device_kind, bench_dir) if traced else None

        config, tr = cell.config, cell.traffic
        self.store, self.sched = build_store(config)
        impl = getattr(self.store.engine, "impl", None)
        log(f"engine: {self.store.engine.name} impl={impl}")
        if require_tpu and impl != "kernel":
            raise BenchError(f"engine resolved to impl={impl!r}, not the "
                             "Pallas kernels")
        self.source = traffic.make_source(seed, config, tr)
        self.inst = None
        if traced:
            from bench.instrument import Instrument
            self.inst = Instrument(self.store, self.sched, self.work)
        self.span = self.inst.span if self.inst else traffic.no_span
        self.kind = tr["kind"]
        self.puts: list = []
        if self.kind == "put_rounds":
            self._setup_puts(tr)
        elif self.kind == "open_get":
            self._setup_gets(tr)
        else:
            raise BenchError(f"unknown traffic kind {self.kind!r}")
        self.setup_s = time.perf_counter() - started
        log(f"set-up: {self.setup_s:.3f} s, {self.compiles.count} "
            "executables")

    def _setup_puts(self, tr: dict) -> None:
        _, self.next_round = traffic.put_rounds(
            self.sched, self.source, 0, None,
            n_rounds=int(tr["setup_rounds"]), log=self.puts)

        def step(_):
            _, self.next_round = traffic.put_rounds(
                self.sched, self.source, self.next_round, None, n_rounds=1,
                log=self.puts)
        _warm(self.compiles, step, int(tr["warm_rounds_min"]),
              int(tr["warm_rounds_max"]), self.log)
        batches = tr.get("warm_engine_batches")
        if batches:
            self._warm_engine(batches, int(tr["warm_engine_draws"]))

    def _warm_engine(self, batches: dict, draws: int) -> None:
        """Warm the batch shapes a flush can meet but warm rounds rarely do.

        A flush hands the engine its whole window, so the batch sizes the
        kernels see (a hash batch's remainder, an encode bucket's count)
        follow the window's chunk counts.  Here the engine's own entry
        points get the cell's own chunks (one flush's worth of the last
        warm round's files, cut on the host by the reference chunker, so
        that no other shape reaches the device) in batches of each size
        the traffic file lists, ``draws`` random draws each.
        """
        eng, cls = self.store.engine, self.store.default_class
        sc = self.cell.config["storage_class"]
        room = int(self.cell.config["scheduler"]["flush_bytes"])
        chunks: list[bytes] = []
        last = self.source.round(self.next_round - 1)
        for data in self.source.contents([key for _, _, key in last]):
            off = 0
            for n in reference.chunk_lengths(data, int(sc["chunk_min"]),
                                             int(sc["chunk_avg"]),
                                             int(sc["chunk_max"])):
                chunks.append(data[off:off + n])
                off += n
            room -= len(data)
            if room <= 0:
                break
        pick = np.random.default_rng([int(self.seed), traffic.PICK, 2])

        def step(_):
            for method, sizes in batches.items():
                for b in sizes:
                    for _ in range(draws):
                        part = [chunks[i] for i in pick.choice(
                            len(chunks), min(int(b), len(chunks)),
                            replace=False)]
                        if method == "encode_blobs_multi":
                            part = [(cls.code, c) for c in part]
                        getattr(eng, method)(part)
        _warm(self.compiles, step, 1, 3, self.log)

    def _setup_gets(self, tr: dict) -> None:
        traffic.put_rounds(self.sched, self.source, 0, None,
                           n_rounds=int(tr["prefill_rounds"]), log=self.puts)
        if not all(p.ok for p in self.puts):
            raise BenchError("prefill puts failed")
        self.files = [(p.user, p.filename) for p in self.puts]
        self.keys = [p.key for p in self.puts]
        for c in self.store.clusters:
            c.kill_nodes([int(i) for i in tr["kill_nodes"]])
        pick = np.random.default_rng([int(self.seed), traffic.PICK, 0])

        def step(_):
            for b in tr["warm_batches"]:
                for i in pick.integers(len(self.files), size=int(b)):
                    user, name = self.files[i]
                    self.sched.submit_get(user, [name])
                self.sched.flush()
        _warm(self.compiles, step, int(tr["warm_passes_min"]),
              int(tr["warm_passes_max"]), self.log)

    def window(self, seconds: float, rate: float | None = None
               ) -> traffic.Window:
        """One measured window, traced when the run is."""
        jax, span, tr = self.jax, self.span, self.cell.traffic
        stats0 = dataclasses.replace(self.sched.stats)
        c0, h0 = self.compiles.count, len(self.compiles.hits)
        self.reduction = None
        if self.traced:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.inst.recording = True
        try:
            with span("bench.window"):
                if self.kind == "put_rounds":
                    w, self.next_round = traffic.put_rounds(
                        self.sched, self.source, self.next_round, seconds,
                        span=span, log=self.puts)
                else:
                    times = traffic.arrivals(
                        rate or float(tr["rate_per_s"]), seconds,
                        int(tr["arrival_order"]))
                    picks = np.random.default_rng(
                        [int(self.seed), traffic.PICK, 1]).integers(
                            len(self.files), size=len(times))
                    w = traffic.open_gets(self.sched, self.files, times,
                                          picks, span=span)
        finally:
            if self.traced:
                self.inst.recording = False
                jax.profiler.stop_trace()
                try:
                    self.reduction = trace.reduce(trace.collect(trace_dir))
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)
        built = self.compiles.names[c0:]
        loaded = len(self.compiles.hits) - h0
        self.log(f"window: {w.seconds:.3f} s on the host clock, "
                 f"{w.attempted} requests, {w.failed} failed, load "
                 f"generator {w.generate_s:.3f} s outside it")
        self.log(f"compiles in window: {len(built) - loaded} "
                 f"(+ {loaded} loaded from the compile cache), "
                 f"{sum(self.compiles.seconds[c0:]):.3f} s "
                 f"{sorted(set(built))}")
        if w.errors:
            self.log(f"first failed request: {w.errors[0]}")
        if w.late_s:
            self.log(f"open-loop sends late by: median "
                     f"{1e3 * float(np.median(w.late_s)):.3f} ms, max "
                     f"{1e3 * max(w.late_s):.3f} ms")
        self.sched_delta = {f.name: getattr(self.sched.stats, f.name)
                            - getattr(stats0, f.name)
                            for f in dataclasses.fields(stats0)}
        return w

    def result(self, w: traffic.Window, benchmark: dict) -> dict:
        """Check the window against the reference; the printed result."""
        dev = self.devices[0]
        mem = dev.memory_stats() or {}
        peak = int(mem.get("peak_bytes_in_use", 0))
        t_ref = time.perf_counter()
        tr = self.cell.traffic
        if self.kind == "put_rounds":
            checks = reference.check_puts(
                self.store, self.puts, self.seed, self.cell.config, tr,
                float(tr["check_fraction"]))
        else:
            checks = reference.check_gets(w, self.source, self.keys)
        checks["requests_failed"] = w.failed
        self.log(f"reference check: {time.perf_counter() - t_ref:.3f} s")
        informative = {"pieces_checked"}
        correct = w.attempted > 0 and all(
            v == 0 for name, v in checks.items() if name not in informative)
        ctx = Context(cell=self.cell, window=w, setup_s=self.setup_s,
                      reduction=self.reduction, instrument=self.inst,
                      work=self.work, peaks=self.peaks,
                      sched_delta=self.sched_delta, kind=self.kind)
        values = {}
        for m in cell_metrics(benchmark, self.cell.name, self.traced):
            v = load_module(self.bench_dir / "metrics"
                            / f"{m['name']}.py").read(ctx)
            if v is not None and np.isfinite(v):
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(self.devices), "memory_peak_bytes": peak}
        out = {"correct": bool(correct), "attempted": w.attempted,
               "failed": w.failed, "metrics": values, "device": device}
        if self.reduction is not None:
            device["busy_s"] = self.reduction["busy_s"]
            device["window_s"] = self.reduction["window_s"]
            out["breakdown"] = self.reduction["breakdown"]
        out["checks"] = {name: {"value": v,
                                "limit": None if name in informative else 0}
                         for name, v in checks.items()}
        return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             benchmark: dict, started: float, require_tpu: bool = True,
             fault: str | None = None, bench_dir: Path = BENCH,
             log=lambda s: print(s, file=sys.stderr)) -> dict:
    """One run, as the command makes it; returns the printed result.

    ``fault`` plants one of ``bench.faults`` after set-up (never in a
    benchmark run).
    """
    run = Run(cell, seed, traced, started=started, require_tpu=require_tpu,
              bench_dir=bench_dir, log=log)
    if fault is not None:
        FAULTS[fault](run.store)
    return run.result(run.window(seconds), benchmark)


def main(argv=None) -> int:
    import argparse
    started = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    try:
        cell = load_cell(args.workload)
        benchmark = _json(ROOT / "BENCHMARK.json")
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       benchmark=benchmark, started=started)
    except (BenchError, FileNotFoundError, ImportError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        limit = "informative" if c["limit"] is None else f"limit {c['limit']}"
        print(f"check {name}: {c['value']} ({limit})", file=sys.stderr)
    print(json.dumps(out))
    return 0
