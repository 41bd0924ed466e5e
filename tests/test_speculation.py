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


# ------------------------------------------- archival nightly backups ----
ARCHIVAL = StorageClass.archival()  # CLB, (14,10), 2/8/16 KiB chunks
SPOT = 4096


def _nights(users, image_bytes, nights, seed):
    """Each user's backup image, night by night: night 0 random, each
    later night the last one with 3% rewritten in 4 KiB spots."""
    rng = np.random.default_rng(seed)
    spots = max(1, int(image_bytes * 0.03) // SPOT)
    images = [rng.integers(0, 256, image_bytes, np.uint8)
              for _ in range(users)]
    out = []
    for night in range(nights):
        if night:
            for img in images:
                for off in rng.integers(0, image_bytes - SPOT, spots):
                    img[off:off + SPOT] = rng.integers(0, 256, SPOT,
                                                       np.uint8)
        out.append([img.tobytes() for img in images])
    return out


def test_fused_archival_nights_match_numpy_and_drop_most_of_the_encode():
    """Four nights of churned backup images through one scheduler per
    store: the fused store ends byte for byte as the numpy store, its
    speculative encode drops nothing on night 0 and most of it after."""
    nights = _nights(users=3, image_bytes=192 << 10, nights=4, seed=11)

    def run(engine):
        store = SEARSStore(classes=[ARCHIVAL], num_clusters=3,
                           node_capacity=1 << 26, engine=engine, seed=3,
                           sanitize=False)
        sched = store.scheduler()
        ups, gots, spec = [], [], []
        for night, images in enumerate(nights):
            e0 = sched.stats.spec_encoded_bytes
            d0 = sched.stats.spec_dropped_bytes
            futures = [sched.submit_put(f"u{u}", [(f"image.night{night}",
                                                   img)])
                       for u, img in enumerate(images)]
            sched.flush()
            ups.append([f.result() for f in futures])
            spec.append((sched.stats.spec_encoded_bytes - e0,
                         sched.stats.spec_dropped_bytes - d0))
            futures = [sched.submit_get(f"u{u}", [f"image.night{night}"])
                       for u in range(len(images))]
            sched.flush()
            gots.append([f.result() for f in futures])
        return store, sched.stats, ups, gots, spec

    sn, _, upn, gotn, specn = run("numpy")
    sf, statf, upf, gotf, specf = run("fused")
    assert upf == upn
    assert gotf == gotn
    for night, images in enumerate(nights):
        assert [got[0][0] for got in gotf[night]] == images
    assert sf.stats() == sn.stats()
    assert all(s == (0, 0) for s in specn)
    # night 0 is distinct random bytes: every chunk encoded is kept
    assert specf[0][0] > 0 and specf[0][1] == 0
    # from night 1 on, the images are ~97% stored already
    for encoded, dropped in specf[1:]:
        assert dropped > encoded / 2
    assert statf.fused_launches > 0
    assert statf.sha1_launches == 0 and statf.gf_launches == 0
