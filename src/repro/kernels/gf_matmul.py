"""Bit-sliced GF(2^8) matmul Pallas kernel (Reed-Solomon encode/decode).

TPU adaptation (DESIGN.md S3): the MXU has no GF(256) mode and per-byte
log/exp table gathers are VPU-serial, so we lift the field matmul to GF(2).
Multiplication by a constant c in GF(2^8) is linear over GF(2) -- an 8x8
0/1 matrix -- so an (r,k) GF(256) coding matrix becomes an (8r, 8k) 0/1
matrix ``Gbits`` and

    C = M (x)_GF256 D        ==        C_bits = (Gbits @ D_bits) mod 2

an ordinary integer matmul (exact in f32: values <= 8k <= 80) followed by
a parity mask -- pure MXU work, zero gathers.  The kernel unpacks data
bytes to bits, runs the (8r, 8k) x (8k, TILE_L) matmul per grid cell, and
repacks bits to bytes, all inside VMEM.

Grid: (B, L / TILE_L) over a batch of B chunk groups with piece length L.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import gf256
from repro.kernels.launches import TRACES

TILE_L = 512  # bytes of piece per grid cell; VMEM ~ 8k*TILE_L*4B


def _kernel(gbits_ref, d_ref, out_ref, *, k: int, r: int):
    # d_ref: (1, k, TILE_L) uint8 -> bits (8k, TILE_L) f32
    d = d_ref[0].astype(jnp.int32)  # (k, TILE_L)
    shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
    dbits = ((d[:, None, :] >> shifts) & 1).reshape(8 * k, d.shape[-1])
    # MXU matmul over GF(2): exact in f32 (max value 8k), then parity.
    acc = jax.lax.dot(gbits_ref[...], dbits.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    cbits = acc.astype(jnp.int32) & 1  # (8r, TILE_L)
    # repack bits -> bytes
    cbits = cbits.reshape(r, 8, -1)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32)).reshape(1, 8, 1)
    out_ref[0] = jnp.sum(cbits * weights, axis=1).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gf_matmul_padded(gbits: jnp.ndarray, data: jnp.ndarray, *,
                      interpret: bool) -> jnp.ndarray:
    """gbits: (8r, 8k) f32; data: (B, k, L) uint8 with L % TILE_L == 0."""
    TRACES.gf += 1  # trace-time only: one increment per compiled shape
    B, k, L = data.shape
    r = gbits.shape[0] // 8
    grid = (B, L // TILE_L)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * r, 8 * k), lambda b, l: (0, 0)),
            pl.BlockSpec((1, k, TILE_L), lambda b, l: (b, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, r, TILE_L), lambda b, l: (b, 0, l)),
        out_shape=jax.ShapeDtypeStruct((B, r, L), jnp.uint8),
        interpret=interpret,
        # the kernel's instruction name in a device trace, kept if this
        # wrapper is renamed (the benchmark's rooflines match it)
        name="_gf_matmul_padded",
    )(gbits, data)


@functools.lru_cache(maxsize=None)
def _gbits_cached(mbytes: bytes, r: int, k: int) -> jnp.ndarray:
    """GF(2) bit-plane lift of an (r,k) coding matrix, memoized by content.

    The lift is pure host work (8r x 8k numpy assembly) that used to run
    on every call; coding matrices come from the lru-cached
    ``rs_code.generator_matrix``/``decode_matrix`` so the working set is a
    handful of entries reused for the life of the process.
    """
    M = np.frombuffer(mbytes, dtype=np.uint8).reshape(r, k)
    return jnp.asarray(gf256.gf_matrix_to_bits(M), dtype=jnp.float32)


def gf_matmul(M: np.ndarray, data: jnp.ndarray, *,
              interpret: bool) -> jnp.ndarray:
    """Apply an (r,k) GF(256) coding matrix to (B, k, L) uint8 pieces.

    Returns (B, r, L) uint8.  ``M`` must be a host numpy matrix (it is
    lifted to its GF(2) bit-matrix once per distinct matrix and cached).
    """
    data = jnp.asarray(data, jnp.uint8)
    if data.ndim == 2:
        data = data[None]
    B, k, L = data.shape
    Mnp = np.ascontiguousarray(np.asarray(M, dtype=np.uint8))
    gbits = _gbits_cached(Mnp.tobytes(), *Mnp.shape)
    pad = (-L) % TILE_L
    if pad:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, pad)))
    out = _gf_matmul_padded(gbits, data, interpret=interpret)
    return out[..., :L]
