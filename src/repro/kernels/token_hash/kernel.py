"""Pallas TPU kernel: batched token fingerprinting (paper §4.1).

Ingest hot path: every log line explodes into dozens of tokens (rules 1-8
n-grams), each needing a 4-byte fingerprint.  On TPU the tokens arrive as
a packed (N, L) byte matrix (padded with zeros); the kernel runs the
polynomial rolling hash across the L byte columns entirely in VMEM on the
VPU — one u32 lane per token — then applies the murmur fmix32 finalizer.

Tiling: grid over N in blocks of ``block_n`` rows; the byte matrix block
(block_n, L) and the length vector block live in VMEM.  All ops are
elementwise u32 — pure 8x128 VPU work, no MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import interpret_mode
from ...core.hashing import POLY_M32, POLY_SEED, _FM32_1, _FM32_2

DEFAULT_BLOCK_N = 1024


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_FM32_1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_FM32_2)
    return h ^ (h >> 16)


def _token_hash_kernel(bytes_ref, len_ref, out_ref, *, max_len: int,
                       seed: int):
    lens = len_ref[...].astype(jnp.int32)            # (bn, 1)
    h = jnp.full(lens.shape, seed, dtype=jnp.uint32)

    def step(j, h):
        byte = bytes_ref[:, j][:, None].astype(jnp.uint32)
        nh = (h * jnp.uint32(POLY_M32)) ^ byte
        return jnp.where(j < lens, nh, h)

    h = jax.lax.fori_loop(0, max_len, step, h)
    out_ref[...] = _fmix32(h ^ lens.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def token_hash_pallas(tokens_u8, lengths, *, block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool | None = None):
    """tokens_u8 (N, L) uint8 zero-padded; lengths (N,) int32.
    Returns (N,) uint32 fingerprints.  N must be a block_n multiple
    (ops.py pads)."""
    n, max_len = tokens_u8.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    out = pl.pallas_call(
        functools.partial(_token_hash_kernel, max_len=max_len,
                          seed=POLY_SEED),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, max_len), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(tokens_u8.astype(jnp.int32), lengths.astype(jnp.int32)[:, None])
    return out[:, 0]
