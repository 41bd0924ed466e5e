"""Batched SHA-1 Pallas kernel.

SHA-1 is sequential over the 64-byte blocks of one message but fully
parallel across messages, so the TPU mapping is lane-parallel: the
message axis is laid out last -- (M, 16, B) words, one message per lane --
and each grid cell processes TILE_B messages; the 80-round compression
runs unrolled on (TILE_B,)-wide uint32 rows (VPU logical/rotate/add ops)
and a ``fori_loop`` walks the message blocks.  Messages shorter than the
padded block count are masked per-lane via ``counts``.

The staged engine pads on the device: :func:`_sha1_flat` takes a batch's
bytes laid out flat by ``hashing.sha1_pack`` (one copy of the chunks,
with per-lane offsets and lengths), builds the padded message schedule
with :func:`message_blocks` and runs the kernel in the same jit.  The
fused ingest still pads on the host (``hashing.sha1_pad_batch``) and
calls :func:`sha1_digest_words`.  Output digests match ``hashlib.sha1``
bit-exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.hashing import FLAT_ROW, SHA1_H0, SHA1_K
from repro.kernels.launches import TRACES

TILE_B = 128  # messages per grid cell

_H0 = SHA1_H0.astype(np.int64)
_K = SHA1_K.astype(np.int64)


def _rotl(x, c):
    return (x << jnp.uint32(c)) | (x >> jnp.uint32(32 - c))


def _compress(h, words):
    """h: 5-tuple of (TILE_B,) uint32; words: (16, TILE_B) uint32."""
    w = [words[t] for t in range(16)]
    for t in range(16, 80):
        w.append(_rotl(w[t - 3] ^ w[t - 8] ^ w[t - 14] ^ w[t - 16], 1))
    a, b, c, d, e = h
    for t in range(80):
        if t < 20:
            f, k = (b & c) | (~b & d), jnp.uint32(_K[0])
        elif t < 40:
            f, k = b ^ c ^ d, jnp.uint32(_K[1])
        elif t < 60:
            f, k = (b & c) | (b & d) | (c & d), jnp.uint32(_K[2])
        else:
            f, k = b ^ c ^ d, jnp.uint32(_K[3])
        tmp = _rotl(a, 5) + f + e + k + w[t]
        e, d, c, b, a = d, c, _rotl(b, 30), a, tmp
    return tuple(x + y for x, y in zip(h, (a, b, c, d, e)))


def _kernel(blocks_ref, counts_ref, out_ref, *, n_blocks: int):
    counts = counts_ref[0]  # (TILE_B,)
    h0 = tuple(jnp.full((counts.shape[0],), jnp.uint32(_H0[i]))
               for i in range(5))

    def body(m, h):
        words = blocks_ref[m]  # (16, TILE_B)
        upd = _compress(h, words)
        live = m < counts
        return tuple(jnp.where(live, u, x) for u, x in zip(upd, h))

    h = jax.lax.fori_loop(0, n_blocks, body, h0)
    out_ref[...] = jnp.stack(h)  # (5, TILE_B)


def _bswap(x):
    return (((x & 0xFF) << 24) | ((x & 0xFF00) << 8)
            | ((x >> 8) & 0xFF00) | (x >> 24))


def message_blocks(flat: jnp.ndarray, offsets: jnp.ndarray,
                   lengths: jnp.ndarray, n_blocks: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """SHA-1 padding on the device, from messages laid out flat.

    ``flat`` is (rows, 128) uint32, the little-endian words of the bytes;
    message ``b`` is ``lengths[b]`` bytes from byte ``offsets[b]``, a
    whole row, and its window of ``ceil(n_blocks / 8)`` rows lies inside
    ``flat`` (``hashing.sha1_flat_rows``).  Each lane's window is one
    gather of whole rows (a vmapped ``dynamic_slice`` of 1-D windows
    compiles to a loop over the lanes on a TPU); then, elementwise, the
    bytes past its length are zeroed, 0x80 follows them and the 64-bit
    bit count ends block ``counts = ceil((len + 9) / 64)``.  Returns
    ((B, n_blocks, 16) uint32 big-endian words, (B,) int32 counts), as
    ``hashing.sha1_pad_batch`` would.
    """
    n_words, row_words = 16 * n_blocks, FLAT_ROW // 4
    B = offsets.shape[0]
    K = -(-n_words // row_words)
    rows = (offsets // FLAT_ROW)[:, None] + jnp.arange(K, dtype=jnp.int32)
    words = _bswap(jnp.take(flat, rows, axis=0, mode="clip")
                   .reshape(B, row_words * K)[:, :n_words])
    n = lengths[:, None]
    j = jnp.arange(n_words, dtype=jnp.int32)[None, :]
    valid = jnp.clip(n - 4 * j, 0, 4)  # message bytes in word j
    keep = jnp.where(valid == 4, jnp.uint32(0xFFFFFFFF),
                     ~(jnp.uint32(0xFFFFFFFF)
                       >> (8 * jnp.minimum(valid, 3)).astype(jnp.uint32)))
    marker = jnp.uint32(0x80) << (8 * (3 - n % 4)).astype(jnp.uint32)
    words = (words & keep) | jnp.where(j == n // 4, marker, jnp.uint32(0))
    counts = (lengths + 9 + 63) // 64
    # the bit count's high word is zero: a message is under 2**29 bytes
    words = jnp.where(j == 16 * counts[:, None] - 1,
                      (8 * n).astype(jnp.uint32), words)
    return words.reshape(B, n_blocks, 16), counts


def _sha1_call(blocks: jnp.ndarray, counts: jnp.ndarray, *, interpret: bool,
               tile: int) -> jnp.ndarray:
    """(B, M, 16) padded blocks + (B,) counts -> (B, 5) digest words."""
    B, M, _ = blocks.shape
    # messages on lanes: laid out messages-first, (B, M, 16), a window-
    # scale batch (4096 x 81 blocks) ran out of scoped VMEM on a v5e
    return pl.pallas_call(
        functools.partial(_kernel, n_blocks=M),
        grid=(B // tile,),
        in_specs=[
            pl.BlockSpec((M, 16, tile), lambda b: (0, 0, b)),
            pl.BlockSpec((1, tile), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((5, tile), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((5, B), jnp.uint32),
        interpret=interpret,
        # the kernel's instruction name in a device trace, kept if this
        # wrapper is renamed (the benchmark's rooflines match it)
        name="_sha1_padded",
    )(blocks.transpose(1, 2, 0), counts.reshape(1, B)).T


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _sha1_padded(blocks: jnp.ndarray, counts: jnp.ndarray, *,
                 interpret: bool, tile: int = TILE_B) -> jnp.ndarray:
    TRACES.sha1 += 1  # trace-time only: one increment per compiled shape
    return _sha1_call(blocks, counts, interpret=interpret, tile=tile)


@functools.partial(jax.jit, static_argnames=("n_blocks", "interpret"))
def _sha1_flat(head: jnp.ndarray, rest: jnp.ndarray, offsets: jnp.ndarray,
               lengths: jnp.ndarray, n_blocks: int, *,
               interpret: bool) -> jnp.ndarray:
    """Flat message bytes (``head`` then ``rest``) -> (B, 5) digest words.

    The padding and the kernel run in one launch.  B lanes pad to a
    TILE_B multiple when past TILE_B, else run as one cell.
    """
    TRACES.sha1 += 1  # trace-time only: one increment per compiled shape
    blocks, counts = message_blocks(jnp.concatenate([head, rest]), offsets,
                                    lengths, n_blocks)
    B = offsets.shape[0]
    tile = min(B, TILE_B)
    pad = -B % tile
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0), (0, 0)))
        counts = jnp.pad(counts, (0, pad))
    return _sha1_call(blocks, counts, interpret=interpret, tile=tile)[:B]


def sha1_digest_words(blocks, counts, *, interpret: bool) -> jnp.ndarray:
    """(B, M, 16) uint32 padded blocks + (B,) counts -> (B, 5) digests.

    Batches of at least TILE_B messages pad to a TILE_B multiple and run
    lane-parallel per grid cell; smaller batches pad to the next power of
    two and run as one narrower cell, so a short steady-state window does
    not drag TILE_B-wide dead lanes through the 80-round compression.
    Either way the compiled-shape set stays bounded (powers of two up to
    TILE_B, then TILE_B-quantized grids).
    """
    blocks = jnp.asarray(blocks, jnp.uint32)
    counts = jnp.asarray(counts, jnp.int32).reshape(-1, 1)
    B = blocks.shape[0]
    if B >= TILE_B:
        tile, padded = TILE_B, B + ((-B) % TILE_B)
    else:
        tile = padded = 1 << max(0, B - 1).bit_length()
    pad = padded - B
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0), (0, 0)))
        counts = jnp.pad(counts, ((0, pad), (0, 0)))
    out = _sha1_padded(blocks, counts, interpret=interpret, tile=tile)
    return out[:B]
