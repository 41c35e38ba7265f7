"""Pallas TPU kernel: CSC sketch probe (baseline of §5, Li et al. [19]).

For each query fingerprint, gathers the p partition bits after each of
the k anchor positions (x j repetitions) and ANDs them.  The entire
(j, m/32) bit plane sits in VMEM (the benchmark sizes CSC at the next
power of two above the DynaWarp sketch — a few MB); per grid step the
kernel evaluates a block of queries against all (rep, k) anchors with a
vectorized gather + shift.

Implemented so the paper's sketch-vs-sketch comparison (DynaWarp probe
vs CSC probe) runs on identical hardware assumptions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import interpret_mode
from ...baselines.csc import _seed
from ...core.hashing import _FM32_1, _FM32_2

DEFAULT_BLOCK_Q = 512


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_FM32_1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_FM32_2)
    return h ^ (h >> 16)


def _csc_kernel(fps_ref, bits_ref, out_ref, *, m: int, k: int, p: int,
                j: int):
    fps = fps_ref[...].astype(jnp.uint32)        # (bq, 1)
    bits = bits_ref[...]                         # (j, m/32)
    mask = jnp.uint32(m - 1)
    out = jnp.ones((fps.shape[0], p), jnp.int32)
    offs = jnp.arange(p, dtype=jnp.int32)[None, :]
    for rep in range(j):
        plane = bits[rep]
        for hk in range(k):
            anchor = (_fmix32(fps ^ jnp.uint32(_seed(rep, hk)))
                      & mask).astype(jnp.int32)  # (bq, 1)
            pos = (anchor + offs) & jnp.int32(m - 1)   # (bq, p)
            w = jnp.take(plane, pos >> 5, axis=0)
            bit = ((w >> (pos & 31).astype(jnp.uint32)) & 1).astype(
                jnp.int32)
            out = out & bit
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("m", "k", "p", "j", "block_q",
                                             "interpret"))
def csc_probe_pallas(fps, bits, *, m: int, k: int, p: int, j: int,
                     block_q: int = DEFAULT_BLOCK_Q,
                     interpret: bool | None = None):
    """fps (Q,) uint32; bits (j, m/32) uint32 -> (Q, p) int32 partition
    survival mask."""
    q = fps.shape[0]
    assert q % block_q == 0
    grid = (q // block_q,)
    out = pl.pallas_call(
        functools.partial(_csc_kernel, m=m, k=k, p=p, j=j),
        grid=grid,
        in_specs=[pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
                  pl.BlockSpec(bits.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_q, p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, p), jnp.int32),
        interpret=interpret_mode(interpret),
    )(fps[:, None], bits)
    return out
