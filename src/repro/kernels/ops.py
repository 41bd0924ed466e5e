"""Public jit'd entry points for the SEARS compute kernels.

Dispatch policy: on TPU backends the Pallas kernels run compiled
(``interpret=False``); on any other backend (CPU test runs) they run in
interpret mode, which executes the same kernel body in Python for
correctness.  ``impl='ref'`` selects the pure-jnp oracle -- useful both for
differential testing and as the default data plane off-TPU, where
interpret mode is Python-slow (see ``engine.KernelEngine``).

Every entry point here is launch-cached: the jitted callables are module
level (so XLA's compile cache keys on shape alone, never on call site) and
host-side matrix conversions -- generator/decode matrices to device arrays
or GF(2) bit-planes -- are memoized by matrix content instead of being
redone per call.  ``LAUNCHES`` counts data-plane dispatches so batching
layers (``core.scheduler``, benchmarks) can prove launch amortization.
"""

from __future__ import annotations

import functools
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.kernels import flash_attn, gear_cdc, gf_matmul, ref, sha1


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# the checkout holding this package (src/repro/kernels/ops.py)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache in a fixed place; return it.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when that is set
    nothing else is set here.  Otherwise the cache is ``.jax_cache`` at
    the root of the checkout (git-ignored): one fixed path, so a later
    process run from the same checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ------------------------------------------------------- launch counting ---
# re-exported for existing callers; the counters themselves live in a
# dependency-free module so readers need not import jax
from repro.kernels.launches import (LAUNCHES, TRACES,  # noqa: E402,F401
                                    LaunchCounter, shipped, span, to_host)


# ---------------------------------------------------------------- GF matmul
@functools.lru_cache(maxsize=None)
def _device_matrix(mbytes: bytes, r: int, k: int) -> jnp.ndarray:
    """Device-resident (r,k) uint8 coding matrix, memoized by content."""
    return jnp.asarray(
        np.frombuffer(mbytes, dtype=np.uint8).reshape(r, k))


def _gf_ref_body(M: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Traced body of the jitted GF oracle (counts its own retraces)."""
    TRACES.gf += 1  # trace-time only: one increment per compiled shape
    return ref.gf_matmul_ref(M, data)


_gf_ref_jit = jax.jit(_gf_ref_body)


def rs_apply(M: np.ndarray, data, impl: str = "kernel") -> jnp.ndarray:
    """Apply an (r,k) GF(256) coding matrix to (B, k, L) uint8 pieces.

    RS encode: M = generator_matrix(n, k)  -> (B, n, L) code pieces.
    RS decode: M = decode_matrix(n, k, received_idx) -> (B, k, L) data.
    """
    LAUNCHES.gf += 1
    with span("sears.engine.dispatch"):
        shipped(data)
        if impl == "ref":
            M = np.ascontiguousarray(np.asarray(M, dtype=np.uint8))
            Mdev = _device_matrix(M.tobytes(), *M.shape)
            return _gf_ref_jit(Mdev, jnp.asarray(data, jnp.uint8))
        return gf_matmul.gf_matmul(M, data, interpret=not _on_tpu())


def rs_encode(code, data, impl: str = "kernel") -> jnp.ndarray:
    """Batched RS encode: (B, k, L) -> (B, n, L) using ``RSCode`` params."""
    from repro.core.rs_code import generator_matrix
    return rs_apply(generator_matrix(code.n, code.k), data, impl=impl)


def rs_decode(code, pieces, indices, impl: str = "kernel") -> jnp.ndarray:
    """Batched RS decode: (B, k, L) received pieces (+ their indices)."""
    from repro.core.rs_code import decode_matrix
    M = decode_matrix(code.n, code.k, tuple(int(i) for i in indices))
    return rs_apply(M, pieces, impl=impl)


# -------------------------------------------- bucketed blob dispatch ------
# Contract: blobs are raw ``bytes``; each is laid out (k, L) uint8 with
# L = code.piece_len(len(blob)) (``rs_code.pack_blob``).  Blobs are
# bucketed by L rounded up to the kernel's TILE_L so one pallas_call
# serves a whole bucket; the batch axis is padded to the next power of
# two to bound the set of compiled (B, k, L) shapes.  Zero pad columns /
# rows are exact under GF(256) (coding is per byte column), so sliced
# results are byte-identical to per-blob host encoding.  Encoding applies
# only the (n-k, k) parity block (``rs_code.parity_matrix``).  The
# bucketing itself lives in ``rs_code.batch_{encode,decode}_blobs``; here
# we only supply the kernel apply_fn and the TPU-shaped padding policy.

def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length() if n > 1 else 1


def rs_encode_blobs(code, blobs: list[bytes],
                    impl: str = "kernel") -> list[list[bytes]]:
    """Batched RS encode of variable-length blobs -> n pieces per blob."""
    from repro.core import rs_code
    from repro.kernels.gf_matmul import TILE_L
    return rs_code.batch_encode_blobs(
        code, blobs, lambda M, arr: rs_apply(M, arr, impl=impl),
        quantum=TILE_L, pad_batch=_pow2)


def rs_decode_blobs(code, jobs: list[tuple[dict[int, bytes], int]],
                    impl: str = "kernel") -> list[bytes]:
    """Batched RS decode; jobs are (piece_map, original_nbytes) pairs.

    Jobs sharing a received-index set and padded length decode in one
    launch (one decode matrix per bucket); systematic arrivals take the
    host-side memcpy fast path.
    """
    from repro.core import rs_code
    from repro.kernels.gf_matmul import TILE_L
    return rs_code.batch_decode_blobs(
        code, jobs, lambda M, arr: rs_apply(M, arr, impl=impl),
        quantum=TILE_L, pad_batch=_pow2)


# ------------------------------------------------------------------ gear ---
@jax.jit
def _gear_ref_padded(data: jnp.ndarray) -> jnp.ndarray:
    """Jit-cached gear oracle; compiles once per bucketed stream length."""
    TRACES.gear += 1  # trace-time only: one increment per compiled shape
    return ref.gear_hash_ref(data)


def gear_hash(data, impl: str = "kernel") -> jnp.ndarray:
    """(N,) uint8 -> (N,) uint32 CDC rolling hash (device-resident result).

    The input is zero-padded to ``gear_cdc.bucket_len`` so varying
    lengths reuse a bounded set of compiled launches (pad positions only
    affect hashes at offsets >= N, which are sliced off -- the gear
    window looks strictly backward).  Counted in ``LAUNCHES.gear``.
    """
    data = np.asarray(data, np.uint8)
    n = data.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    LAUNCHES.gear += 1
    with span("sears.engine.dispatch"):
        if impl == "ref":
            padded = gear_cdc.pad_to_bucket(data)
            shipped(padded)
            return _gear_ref_padded(padded)[:n]
        shipped(data)
        return gear_cdc.gear_hash(data, interpret=not _on_tpu())


def gear_hash_stream(data, impl: str = "kernel") -> np.ndarray:
    """One gear launch over a whole ingest stream -> host (N,) uint32."""
    data = np.asarray(data, np.uint8)
    if data.shape[0] == 0:
        return np.zeros((0,), np.uint32)
    return to_host(gear_hash(data, impl=impl))


@jax.jit
def _gear_fire_ref(data: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Jit-cached fused gear hash + boundary mask test -> packed fire words.

    The kernel's output format (``gear_cdc._fire_kernel``): per TILE of
    the padded stream, (WORDS, LANES) uint32 words whose bit j of word
    [r, l] flags row WORD_BITS * r + j, lane l.
    """
    TRACES.gear += 1  # trace-time only: one increment per compiled shape
    fire = ((ref.gear_hash_ref(data) & mask) == 0).astype(jnp.uint32)
    fire = fire.reshape(-1, gear_cdc.WORDS, gear_cdc.WORD_BITS,
                        gear_cdc.LANES)
    bit = jnp.arange(gear_cdc.WORD_BITS, dtype=jnp.uint32)[:, None]
    return jnp.sum(fire << bit, axis=2, dtype=jnp.uint32)


def gear_fire_issue(data, mask, impl: str = "kernel"):
    """Dispatch one fused gear hash + mask launch; the result stays on device.

    Returns ``(words, n)``: the unmaterialized packed fire bitmap of the
    n-byte stream, one bit per position in the tiles that hold it
    (``gear_cdc.gear_fire``), or ``None`` for an empty stream.  JAX
    dispatch is async, so the caller is free to do host work -- greedy
    boundary selection of the *previous* window, plan building -- while
    the launch runs; ``gear_fire_resolve`` blocks on the words and
    decodes them when they are needed.  Both the Pallas kernel and the
    jitted ref oracle fuse the mask test and the packing into the
    launch, so 1/8 byte per position comes back, never the uint32 hash
    array.
    """
    data = np.asarray(data, np.uint8)
    n = data.shape[0]
    if n == 0:
        return None
    LAUNCHES.gear += 1
    mask = np.uint32(mask)
    with span("sears.engine.dispatch"):
        if impl == "ref":
            padded = gear_cdc.pad_to_bucket(data)
            shipped(padded, mask)
            words = _gear_fire_ref(padded, jnp.uint32(mask))
            return words[:gear_cdc.fire_tiles(n)], n
        shipped(data, mask)
        return gear_cdc.gear_fire(data, mask, interpret=not _on_tpu()), n


def gear_fire_resolve(issued) -> np.ndarray:
    """Materialize issued fire words -> sorted candidate positions."""
    if issued is None:
        return np.zeros(0, np.int64)
    words, n = issued
    words = to_host(words)
    with span("sears.engine.unpack"):
        return gear_cdc.fire_positions(words, n)


def gear_candidate_positions(data, mask, impl: str = "kernel") -> np.ndarray:
    """One gear launch over an ingest stream -> sorted candidate positions.

    The device twin of ``chunking.gear_candidates_np``: the 32-tap hash,
    the boundary mask test and the packing of the flags to one bit per
    position run fused on the device (one bucketed launch; 1/8 byte per
    position shipped back instead of the 4-byte hash array); decoding
    the few nonzero words to positions stays on the host.
    ``gear_fire_issue``/``gear_fire_resolve`` split the same work for
    callers that overlap host work with the launch.
    """
    return gear_fire_resolve(gear_fire_issue(data, mask, impl=impl))


# ----------------------------------------------------------- attention ----
# searslint: ignore[counter-launch] -- not a storage data-plane dispatch
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None):
    """Fused GQA flash attention (Pallas; VMEM-resident running softmax).

    Beyond-paper perf kernel for the attention-bound prefill cells: the
    pure-JAX blockwise path round-trips (m, l, acc) through HBM per KV
    block; this keeps them in VMEM scratch and skips fully-masked causal
    blocks.  q: (B,S,H,hd); k,v: (B,T,KV,hd).
    """
    return flash_attn.flash_attention(q, k, v, causal=causal,
                                      window=window, scale=scale,
                                      interpret=not _on_tpu())


# ------------------------------------------------------------------ sha1 ---
def _sha1_words_loop(blocks: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """SHA-1 oracle body: ``fori_loop`` over blocks and over the 80 rounds.

    Semantically identical to ``ref.sha1_ref``, but the traced body is one
    round, so a bucketed (B, M, 16) launch compiles in O(1) and is reused
    for every subsequent batch.  Looping the rounds as well matters on
    XLA's CPU backend: an unrolled 80-round body ran as hundreds of small
    ops per block (a (64, 129, 16) batch took 16 s on a CPU host, the
    round loop 0.06 s).  Messages lie along the last axis, (16, B) words
    per block.  Shared by the standalone jitted entry point and the fused
    ingest launch (which runs it in the same residency as the GF encode).
    """
    B = blocks.shape[0]
    words = blocks.transpose(1, 2, 0)  # (M, 16, B)
    h0 = jnp.broadcast_to(jnp.asarray(hashing.SHA1_H0.astype(np.int64),
                                      jnp.uint32)[:, None], (5, B))

    def block(m, h):
        def round_(t, st):
            a, b, c, d, e, w = st
            i = t % 16  # w holds the last 16 schedule words
            expanded = ref._rotl(w[(t - 3) % 16] ^ w[(t - 8) % 16]
                                 ^ w[(t - 14) % 16] ^ w[i], 1)
            wt = jnp.where(t < 16, w[i], expanded)
            q = t // 20
            f = jnp.where(q == 0, (b & c) | (~b & d),
                          jnp.where(q == 2, (b & c) | (b & d) | (c & d),
                                    b ^ c ^ d))
            tmp = ref._rotl(a, 5) + f + e + ref._K[q] + wt
            return tmp, a, ref._rotl(b, 30), c, d, w.at[i].set(wt)

        *out, _ = jax.lax.fori_loop(0, 80, round_, (*h, words[m]))
        return jnp.where(m < counts, h + jnp.stack(out), h)

    return jax.lax.fori_loop(0, words.shape[0], block, h0).T


def _sha1_flat_body(head, rest, offsets, lengths, n_blocks):
    """Traced body of the jitted SHA-1 oracle: the device-side padding of
    ``sha1.message_blocks``, then the oracle's loop.

    Kept apart from ``_sha1_words_loop`` so the fused ingest oracle
    (which reuses the loop but counts ``TRACES.fused``) doesn't tick the
    sha1 family.
    """
    TRACES.sha1 += 1  # trace-time only: one increment per compiled shape
    blocks, counts = sha1.message_blocks(jnp.concatenate([head, rest]),
                                         offsets, lengths, n_blocks)
    return _sha1_words_loop(blocks, counts)


_sha1_flat_ref = jax.jit(_sha1_flat_body, static_argnames=("n_blocks",))


@functools.lru_cache(maxsize=None)
def _device_zeros(rows: int) -> jnp.ndarray:
    """Zero (rows, 128) uint32 words kept on the device: the unused part
    of a flat SHA-1 buffer, shipped once per launch shape."""
    return jax.device_put(np.zeros((rows, 128), np.uint32))


def sha1_digests(chunks: list[bytes], impl: str = "kernel") -> list[bytes]:
    """Batched SHA-1 of byte chunks -> 20-byte digests, one launch.

    The batch pads to a power of two of lanes, its block axis to the
    longest chunk's need.
    """
    if not chunks:
        return []
    with span("sears.engine.pack"):
        packed = hashing.sha1_pack(chunks, _pow2(len(chunks)),
                                   max(map(len, chunks)))
    words = to_host(sha1_digest_flat(*packed, impl=impl))
    with span("sears.engine.unpack"):
        return hashing.digest_words_to_bytes(words[:len(chunks)])


def sha1_digest_flat(head: np.ndarray, rest: np.ndarray | None,
                     offsets: np.ndarray, lengths: np.ndarray,
                     n_blocks: int, impl: str = "kernel") -> jnp.ndarray:
    """One SHA-1 launch over a ``hashing.sha1_pack`` batch -> (B, 5) words.

    Ships the flat bytes once (``rest`` only when the batch spills past
    ``head``; otherwise the device's own zeros stand in) with the int32
    offsets and lengths; the message schedule is built on the device.
    """
    LAUNCHES.sha1 += 1
    with span("sears.engine.dispatch"):
        if rest is None:
            rows = hashing.sha1_flat_rows(len(offsets), n_blocks)[1]
            rest = _device_zeros(rows - len(head))
        shipped(head, rest, offsets, lengths)
        if impl == "ref":
            return _sha1_flat_ref(head, rest, offsets, lengths, n_blocks)
        return sha1._sha1_flat(head, rest, offsets, lengths, n_blocks,
                               interpret=not _on_tpu())


# ----------------------------------------------------------- fused ingest --
# One launch per piece-length bucket computing SHA-1 chunk ids AND the RS
# code pieces of the same chunks: the chunk bytes go to the device once
# (laid out (B, k, Lp) for the GF matmul, plus the SHA-1 message schedule)
# and both results come back from a single dispatch, instead of the staged
# path's separate SHA-1 launch + GF launch with a host round-trip between
# them.  Counted in ``LAUNCHES.fused`` (neither .sha1 nor .gf ticks).

# Lanes of one fused launch once a bucket outgrows it.  A 64 MiB window
# of 4 KiB-average chunks puts thousands of chunks in a bucket; padded to
# the next power of two, a count that hovers near one compiles a rare
# shape in the middle of a steady stream of windows.  Launches of exactly
# this many lanes keep one shape per bucket however full the window is.
FUSED_LANES = 1024


@jax.jit
def _fused_ingest_ref(Mdev: jnp.ndarray, blocks: jnp.ndarray,
                      counts: jnp.ndarray, data: jnp.ndarray):
    """Fused jitted oracle: SHA-1 words + GF encode in one dispatch."""
    TRACES.fused += 1  # trace-time only: one increment per compiled shape
    return _sha1_words_loop(blocks, counts), ref.gf_matmul_ref(Mdev, data)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_ingest_pallas(gbits: jnp.ndarray, blocks: jnp.ndarray,
                         counts: jnp.ndarray, data: jnp.ndarray, *,
                         interpret: bool):
    """Fused Pallas path: both kernels issued under one jit (one residency)."""
    TRACES.fused += 1  # trace-time only: one increment per compiled shape
    return (sha1.sha1_digest_words(blocks, counts, interpret=interpret),
            gf_matmul._gf_matmul_padded(gbits, data, interpret=interpret))


def fused_hash_encode_blobs(code, blobs: list[bytes], impl: str = "kernel"
                            ) -> tuple[list[bytes], list[list[bytes]]]:
    """Fused SHA-1 + RS encode of a blob batch -> (ids, pieces per blob).

    Blobs are bucketed by padded piece length like ``rs_encode_blobs``
    (quantum TILE_L).  A bucket of up to ``FUSED_LANES`` blobs is one
    launch with its batch padded to a power of two; a larger one runs
    as launches of exactly ``FUSED_LANES`` lanes, so a window costs
    O(length buckets x chunks / FUSED_LANES) fused launches of a bounded
    shape set.  The SHA-1 message schedule is ``k * Lp`` bytes per
    bucket -- every blob of the bucket fits by construction
    (``piece_len(len) <= Lp``), so there is no oversized-chunk fallback
    on this path.  Like the staged encode it computes and copies back
    only the parity and unpacks with the same ``rs_code.unpack_encoded``:
    byte-identical to running ``sha1_digests`` and ``rs_encode_blobs``
    separately.
    """
    from repro.core import rs_code
    from repro.kernels.gf_matmul import TILE_L
    if not blobs:
        return [], []
    with span("sears.engine.pack"):
        P = np.ascontiguousarray(np.asarray(
            rs_code.parity_matrix(code.n, code.k), dtype=np.uint8))
        piece_lens = [code.piece_len(len(b)) for b in blobs]
        buckets = rs_code.bucket_by_piece_len(piece_lens, TILE_L)
    ids: list[bytes | None] = [None] * len(blobs)
    pieces: list[list[bytes] | None] = [None] * len(blobs)
    launches = []
    for Lp, idxs in buckets.items():
        Bp = min(_pow2(len(idxs)), FUSED_LANES)
        launches += [(Lp, Bp, idxs[lo:lo + Bp])
                     for lo in range(0, len(idxs), Bp)]
    for Lp, Bp, idxs in launches:
        with span("sears.engine.pack"):
            # the GF rows: work a staged engine does only for chunks
            # dedup found new, timed apart as the speculation's host cost
            with span("sears.engine.spec_encode"):
                data = np.zeros((Bp, code.k, Lp), dtype=np.uint8)
                for row, i in enumerate(idxs):
                    data[row] = rs_code.pack_blob(blobs[i], code.k,
                                                  piece_lens[i], Lp)
            group = [blobs[i] for i in idxs] + [b""] * (Bp - len(idxs))
            blocks, counts = hashing.sha1_pad_batch(
                group, max_len=code.k * Lp, exact=True)
        LAUNCHES.fused += 1
        with span("sears.engine.dispatch"):
            shipped(blocks, counts, data)
            if impl == "ref":
                Pdev = _device_matrix(P.tobytes(), *P.shape)
                words, parity = _fused_ingest_ref(
                    Pdev, jnp.asarray(blocks, jnp.uint32),
                    jnp.asarray(counts, jnp.int32), jnp.asarray(data))
            else:
                gbits = gf_matmul._gbits_cached(P.tobytes(), *P.shape)
                words, parity = _fused_ingest_pallas(
                    gbits, jnp.asarray(blocks, jnp.uint32),
                    jnp.asarray(counts, jnp.int32), jnp.asarray(data),
                    interpret=not _on_tpu())
        words, parity = to_host(words), to_host(parity)
        with span("sears.engine.unpack"):
            digests = hashing.digest_words_to_bytes(words[:len(idxs)])
            for row, i in enumerate(idxs):
                ids[i] = digests[row]
            with span("sears.engine.spec_encode"):
                rs_code.unpack_encoded(code, blobs, piece_lens, idxs,
                                       parity, pieces)
    return ids, pieces  # type: ignore[return-value]
