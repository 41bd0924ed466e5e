"""Data-plane coding engines: the hash / RS-encode / RS-decode seam.

The store splits into a *control plane* (chunking, dedup lookups, binding,
placement -- per-chunk metadata work, ``repro.core.pipeline``) and a *data
plane* (bulk byte work over batches of chunks).  ``CodingEngine`` is that
data plane's interface; two implementations:

* ``NumpyEngine`` -- the original per-chunk host path (``hashlib`` SHA-1,
  one GF(256) matmul per chunk).  Reference semantics and fastest on a
  CPU-only container.
* ``KernelEngine`` -- batches chunks into (B, k, L) uint8 arrays (length
  buckets padded to the GF kernel's TILE_L, batch padded to a power of
  two) and dispatches through the Pallas kernels in ``repro.kernels``:
  the bit-sliced GF(256) matmul for encode/decode and the lane-parallel
  SHA-1 kernel for chunk ids.  On TPU it runs the compiled Pallas kernels;
  elsewhere it runs their jitted pure-jnp oracles, so the engine stays
  byte-identical to ``NumpyEngine`` everywhere (proven by the differential
  tests).

Both engines produce identical bytes, so every store-level artifact --
piece placement, dedup ratio, ``StoreStats`` -- is engine-invariant.
"""

from __future__ import annotations

import abc

from repro.core import chunking, hashing
from repro.core.chunking import Chunker
from repro.core.rs_code import RSCode
from repro.kernels.launches import span, to_host


class CodingEngine(abc.ABC):
    """Bulk chunk/hash/encode/decode over batches of files (the data plane)."""

    name: str = "base"

    @abc.abstractmethod
    def chunk_blobs(self, chunker: Chunker,
                    blobs: list[bytes]) -> list[list[tuple[int, int]]]:
        """CDC spans for a batch of files: one rolling-hash pass per window.

        Returns per-blob ``[(offset, length), ...]`` lists, byte-identical
        to ``chunker.chunk_spans`` on each blob individually.
        """

    @abc.abstractmethod
    def hash_chunks(self, chunks: list[bytes]) -> list[bytes]:
        """Chunk ids (20-byte SHA-1 by default) for a batch of chunks."""

    @abc.abstractmethod
    def encode_blobs(self, code: RSCode,
                     blobs: list[bytes]) -> list[list[bytes]]:
        """RS-encode each blob into n pieces."""

    @abc.abstractmethod
    def decode_blobs(self, code: RSCode,
                     jobs: list[tuple[dict[int, bytes], int]]
                     ) -> list[bytes]:
        """Reconstruct each blob from (piece_map, nbytes) jobs."""

    def recode_blobs(self, code: RSCode,
                     jobs: list[tuple[dict[int, bytes], int]]
                     ) -> tuple[list[bytes], list[list[bytes]]]:
        """Repair path: decode (piece_map, nbytes) jobs, re-encode to n.

        One decode batch plus one encode batch, so a repair sub-batch
        costs O(length buckets) launches regardless of how many chunks --
        across how many clusters -- it carries.  Returns ``(blobs,
        pieces_per_blob)``; shared by both engines through their batched
        ``decode_blobs``/``encode_blobs``.
        """
        blobs = self.decode_blobs(code, jobs)
        return blobs, self.encode_blobs(code, blobs)

    # -- heterogeneous batches: one window, many storage-class policies ----
    # A mixed-class flush window carries work under several (n, k) codes
    # and several chunker configs at once.  The *_multi entry points keep
    # the window's launch economics: they group by policy and issue one
    # batched call per group, so a window costs O(code buckets x length
    # buckets) GF launches and O(chunker configs) gear launches -- never
    # O(files) or O(chunks).  Results come back in input order.

    def _by_policy(self, jobs: list[tuple], batch_fn) -> list:
        """Group (policy, *job) tuples by policy, run one batched call per
        group, and scatter results back into input order.  ``batch_fn``
        receives the policy and that group's job payloads (the tuple
        remainders, unwrapped when they are single values)."""
        groups: dict = {}
        for i, job in enumerate(jobs):
            groups.setdefault(job[0], []).append(i)
        out: list = [None] * len(jobs)
        for policy, idxs in groups.items():
            payload = [jobs[i][1] if len(jobs[i]) == 2 else jobs[i][1:]
                       for i in idxs]
            for i, res in zip(idxs, batch_fn(policy, payload)):
                out[i] = res
        return out

    def chunk_blobs_multi(self, jobs: list[tuple[Chunker, bytes]]
                          ) -> list[list[tuple[int, int]]]:
        """CDC spans for (chunker, blob) jobs: one gear pass per chunker."""
        return self._by_policy(jobs, self.chunk_blobs)

    def encode_blobs_multi(self, jobs: list[tuple[RSCode, bytes]]
                           ) -> list[list[bytes]]:
        """RS-encode (code, blob) jobs: one encode batch per distinct code."""
        return self._by_policy(jobs, self.encode_blobs)

    def decode_blobs_multi(self,
                           jobs: list[tuple[RSCode, dict[int, bytes], int]]
                           ) -> list[bytes]:
        """Decode (code, piece_map, nbytes) jobs, one batch per code."""
        return self._by_policy(jobs, self.decode_blobs)

    def recode_blobs_multi(self,
                           jobs: list[tuple[RSCode, dict[int, bytes], int]]
                           ) -> tuple[list[bytes], list[list[bytes]]]:
        """Repair recode of (code, piece_map, nbytes) jobs across codes.

        One decode + one encode batch per distinct code, so a cross-class
        repair sub-batch stays O(code buckets x length buckets) launches.
        """
        paired = self._by_policy(
            jobs, lambda code, group: list(zip(*self.recode_blobs(
                code, group))))
        blobs = [b for b, _ in paired]
        pieces = [p for _, p in paired]
        return blobs, pieces

    # -- fused ingest seam -------------------------------------------------
    # ``supports_fused_ingest`` advertises a hash+encode path that keeps
    # each chunk resident on the device for both passes (one launch per
    # bucket instead of separate SHA-1 and GF dispatches).  The staged
    # default below is the semantic contract the fused override must
    # match byte-for-byte (differential-tested in tests/test_ingest.py).

    supports_fused_ingest: bool = False

    def hash_encode_blobs_multi(self, jobs: list[tuple[RSCode, bytes]]
                                ) -> tuple[list[bytes], list[list[bytes]]]:
        """Chunk ids + RS pieces for (code, blob) jobs, input order.

        Staged reference semantics: hash everything, then encode
        everything.  ``FusedEngine`` overrides this with the single-
        residency fused path.
        """
        ids = self.hash_chunks([blob for _, blob in jobs])
        return ids, self.encode_blobs_multi(jobs)

    # -- begin/finish splits: the double-buffering seam --------------------
    # ``*_begin`` issues a window's device work (or defers host work) and
    # returns an opaque token; ``*_finish`` materializes results.  The
    # base defaults defer everything to finish time -- correct for any
    # engine -- so the pipelined store paths work unchanged on
    # ``NumpyEngine``; ``KernelEngine`` overrides them to genuinely issue
    # launches ahead (JAX async dispatch), which is where the overlap
    # comes from.

    def chunk_blobs_begin(self, chunker: Chunker, blobs: list[bytes]):
        """Stage a window's CDC pass; resolve with ``chunk_blobs_finish``."""
        return (chunker, blobs)

    def chunk_blobs_finish(self, pending) -> list[list[tuple[int, int]]]:
        return self.chunk_blobs(*pending)

    def chunk_blobs_multi_begin(self, jobs: list[tuple[Chunker, bytes]]):
        """Stage a mixed-chunker window; resolve with the finish twin."""
        return jobs

    def chunk_blobs_multi_finish(self, token) -> list[list[tuple[int, int]]]:
        return self.chunk_blobs_multi(token)

    def decode_blobs_multi_begin(
            self, jobs: list[tuple[RSCode, dict[int, bytes], int]]):
        """Stage a decode window; resolve with ``decode_blobs_multi_finish``."""
        return jobs

    def decode_blobs_multi_finish(self, token) -> list[bytes]:
        return self.decode_blobs_multi(token)

    def _by_policy_begin(self, jobs: list[tuple], begin_fn):
        """Begin-side half of ``_by_policy``: group by policy, issue one
        ``begin_fn(policy, payload)`` per group, keep the scatter plan."""
        groups: dict = {}
        for i, job in enumerate(jobs):
            groups.setdefault(job[0], []).append(i)
        started = []
        for policy, idxs in groups.items():
            payload = [jobs[i][1] if len(jobs[i]) == 2 else jobs[i][1:]
                       for i in idxs]
            started.append((idxs, begin_fn(policy, payload)))
        return (len(jobs), started)

    def _by_policy_finish(self, token, finish_fn) -> list:
        """Finish-side half: resolve each group and scatter to input order."""
        n, started = token
        out: list = [None] * n
        for idxs, pending in started:
            for i, res in zip(idxs, finish_fn(pending)):
                out[i] = res
        return out


class NumpyEngine(CodingEngine):
    """Per-chunk host path: hashlib + one numpy GF matmul per chunk."""

    name = "numpy"

    def __init__(self, hash_fn=hashing.chunk_id) -> None:
        self.hash_fn = hash_fn

    def chunk_blobs(self, chunker: Chunker,
                    blobs: list[bytes]) -> list[list[tuple[int, int]]]:
        # vectorized host path: one fused gear pass over the whole window
        return chunking.chunk_spans_batch(chunker, blobs,
                                          chunking.gear_candidates_np)

    def hash_chunks(self, chunks: list[bytes]) -> list[bytes]:
        return [self.hash_fn(c) for c in chunks]

    def encode_blobs(self, code: RSCode,
                     blobs: list[bytes]) -> list[list[bytes]]:
        return [code.encode_bytes(b) for b in blobs]

    def decode_blobs(self, code: RSCode, jobs) -> list[bytes]:
        return [code.decode_bytes(pieces, nbytes) for pieces, nbytes in jobs]


class KernelEngine(CodingEngine):
    """Batched Pallas path: length-bucketed GF matmul + lane-parallel SHA-1.

    ``impl='kernel'`` runs the Pallas kernels; ``impl='ref'`` selects the
    jit-compiled pure-jnp oracles -- same batching, same bytes.  The
    default (``impl=None``) is backend-aware: Pallas on TPU, ``'ref'``
    everywhere else, because interpret-mode Pallas executes the kernel
    body in Python per grid cell and is orders of magnitude slower than
    the XLA-compiled oracle on CPU.

    SHA-1 launches use a fixed batch of ``hash_batch`` messages padded to
    at most ``max_hash_len`` bytes of message schedule, so the compiled
    (B, M, 16) shapes stay bounded regardless of workload.  Every chunk is
    hashed on the device: a chunk longer than ``max_hash_len`` raises
    ``ValueError``, so the store sizes the cap to its classes' largest
    ``chunk_max``.  Only a custom ``hash_fn`` (which has no kernel twin)
    hashes on the host.
    """

    name = "kernel"

    HASH_BATCH = 512

    def __init__(self, hash_fn=hashing.chunk_id, impl: str | None = None,
                 max_hash_len: int = 8192,
                 hash_batch: int | None = None) -> None:
        self.hash_fn = hash_fn
        if impl is None:
            import jax
            impl = "kernel" if jax.default_backend() == "tpu" else "ref"
            if impl == "kernel":
                from repro.kernels import ops
                ops.use_compile_cache()
        self.impl = impl
        self.max_hash_len = max_hash_len
        self.hash_batch = hash_batch or self.HASH_BATCH

    def chunk_blobs_begin(self, chunker: Chunker, blobs: list[bytes]):
        """Issue the window's gear launch; the bitmap stays on device."""
        from repro.kernels import ops
        return chunking.chunk_spans_batch_begin(
            chunker, blobs,
            lambda stream, mask: ops.gear_fire_issue(
                stream, mask, impl=self.impl))

    def chunk_blobs_finish(self, pending) -> list[list[tuple[int, int]]]:
        """Block on the fire bitmap; greedy selection on host."""
        from repro.kernels import ops
        return chunking.chunk_spans_batch_finish(
            pending, ops.gear_fire_resolve)

    def chunk_blobs(self, chunker: Chunker,
                    blobs: list[bytes]) -> list[list[tuple[int, int]]]:
        """One device gear launch per window; greedy selection on host."""
        return self.chunk_blobs_finish(self.chunk_blobs_begin(chunker, blobs))

    def chunk_blobs_multi_begin(self, jobs: list[tuple[Chunker, bytes]]):
        """Issue one gear launch per distinct chunker, all in flight."""
        return self._by_policy_begin(jobs, self.chunk_blobs_begin)

    def chunk_blobs_multi_finish(self, token) -> list[list[tuple[int, int]]]:
        return self._by_policy_finish(token, self.chunk_blobs_finish)

    def decode_blobs_multi_begin(
            self, jobs: list[tuple[RSCode, dict[int, bytes], int]]):
        """Issue decode launches per code; arrays stay unmaterialized."""
        from repro.kernels import ops
        return self._by_policy_begin(
            jobs, lambda code, group: ops.rs_decode_blobs_begin(
                code, group, impl=self.impl))

    def decode_blobs_multi_finish(self, token) -> list[bytes]:
        from repro.kernels import ops
        return self._by_policy_finish(token, ops.rs_decode_blobs_finish)

    def hash_chunks(self, chunks: list[bytes]) -> list[bytes]:
        if self.hash_fn is not hashing.chunk_id:
            # custom id functions have no kernel twin -- host fallback
            return [self.hash_fn(c) for c in chunks]
        from repro.kernels import ops
        out: list[bytes] = []
        for i in range(0, len(chunks), self.hash_batch):
            group = chunks[i: i + self.hash_batch]
            with span("sears.engine.pack"):
                # pad the batch axis to the next power of two (clamped to
                # hash_batch): a steady-state window of tens of chunks no
                # longer drags hash_batch-wide dead lanes through the
                # compression loop, and the compiled-shape set stays
                # bounded ({1, 2, 4, ..., hash_batch} x bucketed block
                # widths)
                target = min(1 << max(0, len(group) - 1).bit_length(),
                             self.hash_batch)
                pad = target - len(group)
                # raises ValueError for a chunk over max_hash_len: growing
                # the compiled block axis, or hashing it on the host,
                # would hide it
                blocks, counts = hashing.sha1_pad_batch(
                    group + [b""] * pad, max_len=self.max_hash_len)
            words = to_host(ops.sha1_digest_words(blocks, counts,
                                                  impl=self.impl))
            with span("sears.engine.unpack"):
                out += hashing.digest_words_to_bytes(words[:len(group)])
        return out

    def encode_blobs(self, code: RSCode,
                     blobs: list[bytes]) -> list[list[bytes]]:
        from repro.kernels import ops
        return ops.rs_encode_blobs(code, blobs, impl=self.impl)

    def decode_blobs(self, code: RSCode, jobs) -> list[bytes]:
        from repro.kernels import ops
        return ops.rs_decode_blobs(code, jobs, impl=self.impl)


class FusedEngine(KernelEngine):
    """KernelEngine plus the fused single-residency ingest path.

    Inherits all batched entry points; ``hash_encode_blobs_multi`` is
    replaced by the fused SHA-1 + GF-encode dispatch
    (``kernels.ops.fused_hash_encode_blobs``): each chunk is packed into
    device-resident (B, k, L) form once and both passes run inside one
    jitted launch per piece-length bucket, so a put window costs
    1 gear + O(piece-length buckets) launches instead of
    1 gear + 1 SHA-1 + O(length buckets) GF.  Encoding is speculative --
    every unique chunk of the window is encoded before the dedup lookup
    decides whether its pieces are needed -- which trades a few wasted
    device FLOPs for the removed round-trip.  Byte-identical to the
    staged path (differential-tested), and the store falls back to
    staged ``hash_chunks`` + ``encode_blobs_multi`` automatically when
    ``supports_fused_ingest`` is false (custom ``hash_fn``).
    """

    name = "fused"

    @property
    def supports_fused_ingest(self) -> bool:  # type: ignore[override]
        # the fused kernel computes SHA-1; a custom id function has no
        # device twin, so the store must take the staged fallback
        return self.hash_fn is hashing.chunk_id

    def hash_encode_blobs_multi(self, jobs: list[tuple[RSCode, bytes]]
                                ) -> tuple[list[bytes], list[list[bytes]]]:
        if not self.supports_fused_ingest:
            return super().hash_encode_blobs_multi(jobs)
        from repro.kernels import ops
        ids: list = [None] * len(jobs)
        pieces: list = [None] * len(jobs)
        # intra-window duplicates (same code, same bytes) cost one lane;
        # RSCode is a frozen dataclass, so value-equal codes coalesce
        rep: dict = {}
        for i, (code, blob) in enumerate(jobs):
            rep.setdefault((code, blob), i)
        groups: dict = {}
        for (code, _), i in rep.items():
            groups.setdefault(code, []).append(i)
        for code, idxs in groups.items():
            gids, gpieces = ops.fused_hash_encode_blobs(
                code, [jobs[i][1] for i in idxs], impl=self.impl)
            for i, cid, ps in zip(idxs, gids, gpieces):
                ids[i], pieces[i] = cid, ps
        for i, (code, blob) in enumerate(jobs):
            if ids[i] is None:
                j = rep[(code, blob)]
                ids[i], pieces[i] = ids[j], pieces[j]
        return ids, pieces


def make_engine(spec, hash_fn=hashing.chunk_id,
                max_hash_len: int = 8192) -> CodingEngine:
    """Resolve an engine spec to a ``CodingEngine``.

    Accepted specs: a ``CodingEngine`` instance, ``'numpy'`` (per-chunk
    host path), ``'kernel'`` (batched; backend-aware -- Pallas kernels on
    TPU, jitted ``'ref'`` oracles elsewhere), ``'fused'`` (kernel
    batching plus the fused single-residency hash+encode ingest), or the
    explicit overrides ``'ref'`` / ``'pallas'`` that pin the batched
    implementation regardless of backend.  ``max_hash_len`` is the
    longest chunk the batched engines must hash on the device; a given
    instance whose cap is shorter is refused.
    """
    if isinstance(spec, CodingEngine):
        if getattr(spec, "max_hash_len", max_hash_len) < max_hash_len:
            raise ValueError(
                f"engine hashes chunks up to {spec.max_hash_len} bytes, "
                f"the store's classes need {max_hash_len}")
        return spec
    if spec == "numpy":
        return NumpyEngine(hash_fn)
    if spec == "kernel":  # impl resolved from backend
        return KernelEngine(hash_fn, max_hash_len=max_hash_len)
    if spec == "fused":  # impl resolved from backend
        return FusedEngine(hash_fn, max_hash_len=max_hash_len)
    if spec == "ref":
        return KernelEngine(hash_fn, impl="ref", max_hash_len=max_hash_len)
    if spec == "pallas":
        return KernelEngine(hash_fn, impl="kernel",
                            max_hash_len=max_hash_len)
    raise ValueError(f"unknown coding engine {spec!r}")
