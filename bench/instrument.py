"""Host spans and work records around the store's layers (traced runs).

The benchmark wraps the methods of one store's engine instance and of its
scheduler's ``flush``; the program is not edited.  Each wrapped call runs
inside a ``jax.profiler.TraceAnnotation`` (``bench.engine.<method>``,
``bench.flush``), so the device trace can say what the host was doing in
each idle gap, and adds its host time to a total: engine time counts the
outermost engine call only.  Work modules (``bench/work/*.py``) name the
engine methods whose arguments carry their kernel's work; while
``recording`` is on, each such call's arguments become work descriptors.
"""

from __future__ import annotations

import functools
import time

# the engine's data-plane entry points the store calls
ENGINE_METHODS = ("chunk_blobs_multi_begin", "chunk_blobs_multi_finish",
                  "hash_chunks", "encode_blobs_multi", "decode_blobs_multi",
                  "decode_blobs_multi_begin", "decode_blobs_multi_finish",
                  "hash_encode_blobs_multi")


class Instrument:
    def __init__(self, store, sched, work_modules: dict) -> None:
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.engine_s = 0.0
        self.flush_s = 0.0
        self.recording = False
        self.calls: dict[str, list] = {name: [] for name in work_modules}
        self._depth = 0
        engine = store.engine
        by_method: dict[str, list] = {}
        for name, mod in work_modules.items():
            for method in mod.ENGINE_CALLS:
                by_method.setdefault(method, []).append((name, mod))
        for method in set(ENGINE_METHODS) | set(by_method):
            fn = getattr(engine, method, None)
            if fn is not None:
                setattr(engine, method,
                        self._wrap_engine(method, fn, by_method.get(method,
                                                                    [])))
        self._wrap_flush(sched)

    def span(self, name: str):
        return self._annotation(name)

    def _wrap_engine(self, method, fn, readers):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if self.recording:
                for name, mod in readers:
                    self.calls[name].extend(mod.calls(method, args, kwargs))
            self._depth += 1
            t0 = time.perf_counter()
            try:
                with self._annotation(f"bench.engine.{method}"):
                    return fn(*args, **kwargs)
            finally:
                self._depth -= 1
                if self._depth == 0 and self.recording:
                    self.engine_s += time.perf_counter() - t0
        return call

    def _wrap_flush(self, sched) -> None:
        flush = sched.flush

        @functools.wraps(flush)
        def call():
            t0 = time.perf_counter()
            try:
                with self._annotation("bench.flush"):
                    return flush()
            finally:
                if self.recording:
                    self.flush_s += time.perf_counter() - t0
        sched.flush = call
