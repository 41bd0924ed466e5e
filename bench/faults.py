"""Faults planted under the timed path, to show that ``correct`` sees them.

None of these runs in a benchmark run.  ``bench/control.py`` runs them on
the chip at a cell's own size, and ``bench/tests`` on the CPU at a small
one; each must turn ``correct`` false.  A fault wraps methods of one
store's engine or clusters, as the program's own output would be wrong:

* ``parity_zeroed`` (the put cells' control): parity pieces are written
  as zeros of the right length, so a put is acknowledged without the
  redundancy that survives n-k lost pieces.
* ``decode_skipped`` (the degraded-get cell's control): a get returns the
  first k pieces it read, joined, instead of decoding them.
* ``state_unchanged``: puts land no piece; gets decode to empty bytes.
* ``half_batch``: only the first half of each encode or decode batch is
  computed; the rest come back as zeros.
* ``answer_altered``: one byte of the first piece or blob of each encode
  or decode batch is flipped where it is produced.
"""

from __future__ import annotations

import functools


def _flip(b: bytes) -> bytes:
    return bytes([b[0] ^ 1]) + b[1:] if b else b"\x01"


def _wrap(obj, method: str, make):
    fn = getattr(obj, method)
    setattr(obj, method, functools.wraps(fn)(make(fn)))


def _encode(store, alter) -> None:
    """Rewrite the pieces ``encode_blobs_multi`` returns."""
    def make(fn):
        def call(jobs):
            out = fn(jobs)
            return [alter(i, len(out), (code.n, code.k), pieces)
                    for i, ((code, _), pieces) in enumerate(zip(jobs, out))]
        return call
    _wrap(store.engine, "encode_blobs_multi", make)


def _decode(store, alter) -> None:
    """Rewrite the blobs ``decode_blobs_multi`` returns."""
    def make(fn):
        def call(jobs):
            out = fn(jobs)
            return [alter(i, len(out), job, blob)
                    for i, (job, blob) in enumerate(zip(jobs, out))]
        return call
    _wrap(store.engine, "decode_blobs_multi", make)


def parity_zeroed(store) -> None:
    _encode(store, lambda i, m, nk, ps: ps[:nk[1]]
            + [bytes(len(p)) for p in ps[nk[1]:]])


def decode_skipped(store) -> None:
    def make(fn):
        def call(jobs):
            return [b"".join(pieces[j] for j in sorted(pieces)[:code.k])
                    [:nbytes] for code, pieces, nbytes in jobs]
        return call
    _wrap(store.engine, "decode_blobs_multi", make)


def state_unchanged(store) -> None:
    for cluster in store.clusters:
        def make(fn, cluster=cluster):
            def call(items, min_pieces=None, reserved=0):
                cluster.release_reservation(reserved)
            return call
        _wrap(cluster, "store_chunks", make)
    _decode(store, lambda i, m, job, blob: b"")


def half_batch(store) -> None:
    _encode(store, lambda i, m, nk, ps: ps if i < m // 2
            else [bytes(len(p)) for p in ps])
    _decode(store, lambda i, m, job, blob: blob if i < m // 2
            else bytes(len(blob)))


def answer_altered(store) -> None:
    _encode(store, lambda i, m, nk, ps: [_flip(ps[0])] + ps[1:] if i == 0
            else ps)
    _decode(store, lambda i, m, job, blob: _flip(blob) if i == 0 else blob)


FAULTS = {f.__name__: f for f in (parity_zeroed, decode_skipped,
                                  state_unchanged, half_batch,
                                  answer_altered)}
