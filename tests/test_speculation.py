"""Bytes a fused engine RS-encodes before dedup, and the part of them no
upload takes: ``SchedulerStats.spec_encoded_bytes`` and
``spec_dropped_bytes``, folded per flush from ``launches.SPECULATION``.
On the CPU the fused engine runs its jitted oracles (``impl="ref"``)."""

import numpy as np
import pytest

from repro.core import SEARSStore
from repro.core.classes import StorageClass

CLASS = StorageClass(name="realtime", n=10, k=5, chunk_min=1024,
                     chunk_avg=4096, chunk_max=8192, binding="ulb")


def _sched(engine):
    store = SEARSStore(classes=[CLASS], num_clusters=3,
                       node_capacity=1 << 26, engine=engine, sanitize=False)
    return store.scheduler()


def _data(n, seed):
    # random bytes: every chunk of a file is distinct
    return np.random.default_rng(seed).integers(
        0, 256, n, np.uint8).tobytes()


def _flush(sched, *puts):
    """One flush of ``puts`` ((user, name, data) each); the delta of
    both counters over it."""
    e0 = sched.stats.spec_encoded_bytes
    d0 = sched.stats.spec_dropped_bytes
    futures = [sched.submit_put(user, [(name, data)])
               for user, name, data in puts]
    sched.flush()
    for f in futures:
        f.result()
    return (sched.stats.spec_encoded_bytes - e0,
            sched.stats.spec_dropped_bytes - d0)


def test_a_stored_copy_drops_the_speculative_encode():
    sched = _sched("fused")
    data = _data(40_000, seed=1)
    assert _flush(sched, ("u", "a", data)) == (len(data), 0)
    # the same bytes by the same user under another name: every chunk is
    # a dedup hit, so all of its encode is thrown away
    assert _flush(sched, ("u", "b", data)) == (len(data), len(data))


def test_window_duplicates_encode_once_and_are_kept():
    sched = _sched("fused")
    data = _data(30_000, seed=2)
    assert _flush(sched, ("u", "a", data), ("u", "b", data)) == (
        len(data), 0)


def test_a_copy_deleted_later_in_the_window_is_dropped():
    sched = _sched("fused")
    first, second = _data(20_000, seed=3), _data(24_000, seed=4)
    # the second request overwrites the first's file before its pieces
    # land: the first file's chunks were encoded for nothing
    assert _flush(sched, ("u", "a", first), ("u", "a", second)) == (
        len(first) + len(second), len(first))


@pytest.mark.parametrize("engine", ["kernel", "numpy"])
def test_staged_engines_encode_nothing_ahead(engine):
    sched = _sched(engine)
    data = _data(30_000, seed=5)
    assert _flush(sched, ("u", "a", data)) == (0, 0)
    assert _flush(sched, ("u", "b", data)) == (0, 0)
