"""Sharded device retrieval — the paper's horizontal scaling (§3, §6)
mapped onto the production mesh, THROUGH the batched query engine.

Grail assigns immutable segments to query workers; a query fans out to
every segment's sketch and unions/intersects the per-segment candidate
sets.  Here that becomes segment parallelism over the mesh:

  * whole segments are assigned to mesh shards (``('pod', 'data')``
    segment parallelism) — each segment's padded flat buffers upload to
    its shard's device exactly once (:meth:`ImmutableSketch.
    device_row_cache`, the sharded twin of ``device_cache``) and
    survive engine rebuilds, so compaction re-uploads only merged
    segments,
  * segments group into *level-layout buckets* (identical MPHF level
    metadata + padded array geometry).  A bucket's rows stack into
    (S, ...) device arrays sharded over the segment axis — assembled
    zero-copy from the per-device rows via
    ``jax.make_array_from_single_device_arrays`` — and one
    ``shard_map`` evaluates the whole (Q, T) wave: every shard probes
    its local segments with the SAME probe code path the
    single-device engine uses (:func:`core.immutable_sketch.
    match_bitmap_from`) and OR-accumulates its local token planes,
  * the only cross-shard traffic is the final all-gather of per-shard
    (Q, T, W) partial bitmaps, OR-folded before the engine's shared
    reduce (``bitset_ops``) + device candidate extraction
    (``bitmap_extract``) stages.

Heterogeneous fleets shard too: segments whose level layouts differ
land in separate buckets (one dispatch per bucket), so no segment ever
falls back to a host unroll.  Semantics are bit-identical to
:class:`~repro.core.query_engine.QueryEngine` — same probe kernels,
same fan-out OR, same reduce/extract, one code path.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from .immutable_sketch import match_bitmap_from
from .query_engine import QueryEngine

# names stacked into the per-shard buffers, with their pad fill
_ROW_FILL = {
    "words": 0, "block_rank": 0,
    "fallback_fps": 0xFFFFFFFF, "fallback_idx": 0,
    "signatures": 0,
    "csf_bitseq": 0, "csf_lengths": 0, "csf_samples": 0,
}
_ROW_SCALARS = ("fb_count", "n_tokens1", "n_lists1", "active")


def _p2(n: int) -> int:
    """Next power of two >= max(n, 1) — per-array pad geometry that is a
    property of the segment alone, so cached rows survive bucket-mate
    churn (a merged-away neighbour never invalidates this segment)."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def _pad1(a: np.ndarray, size: int, fill=0) -> np.ndarray:
    out = np.full(size, fill, a.dtype)
    out[:a.size] = a
    return out


def default_shard_mesh(shard_axes=("data",)):
    """A mesh over every visible device, named by ``shard_axes`` (extra
    leading axes get size 1 — ``('pod', 'data')`` works on one host).
    Its axes are ``Auto``: the wave's placement is spelled out by the
    shard_map specs, and arrays leaving it carry no sharding in their
    types into the single-device reduce and extract kernels."""
    n = len(jax.devices())
    shape = (1,) * (len(shard_axes) - 1) + (n,)
    return jax.make_mesh(shape, tuple(shard_axes),
                         axis_types=(AxisType.Auto,) * len(shard_axes))


class ShardedQueryEngine(QueryEngine):
    """Segment-parallel :class:`QueryEngine`: same wave semantics, with
    the plane-backed probe fan-out distributed over a device mesh."""

    def __init__(self, segments, *, mesh=None, shard_axes=("data",),
                 n_postings: int | None = None, lru_lists: int = 4096,
                 extract_on_device: bool | None = None):
        super().__init__(segments, n_postings=n_postings,
                         lru_lists=lru_lists,
                         extract_on_device=extract_on_device)
        self.shard_axes = tuple(shard_axes)
        if mesh is None:
            mesh = default_shard_mesh(self.shard_axes)
        self.mesh = mesh
        self.n_shards = math.prod(mesh.shape[a] for a in self.shard_axes)
        self._shard_devices = self._devices_by_shard()
        self._assign_shards()
        self._buckets = self._build_buckets()
        self._bucket_arrs: dict[tuple, tuple] = {}
        self._wave_fn_cached = None

    # ------------------------------------------------------------ placement
    def _devices_by_shard(self) -> list[list]:
        """Mesh devices grouped by linear shard index along
        ``shard_axes`` (devices along non-shard axes are replicas)."""
        names = list(self.mesh.axis_names)
        shard_dims = [names.index(a) for a in self.shard_axes]
        sizes = [self.mesh.shape[a] for a in self.shard_axes]
        groups: list[list] = [[] for _ in range(self.n_shards)]
        devs = np.asarray(self.mesh.devices)
        for pos in np.ndindex(devs.shape):
            coord = tuple(pos[d] for d in shard_dims)
            groups[int(np.ravel_multi_index(coord, sizes))] \
                .append(devs[pos])
        return groups

    def _assign_shards(self) -> None:
        """Stable segment -> shard slots: a segment keeps the slot it was
        first given (its uploaded rows stay valid across engine rebuilds;
        durable segments keep it across store reopens too, keyed by their
        durable id); new segments fill the least-loaded shards."""
        load = [0] * self.n_shards
        fresh = []
        for _, seg in self._plane_segs:
            slot = seg.get_shard_slot()
            if slot is not None and slot < self.n_shards:
                load[slot] += 1
            else:
                fresh.append(seg)
        for seg in fresh:
            slot = int(np.argmin(load))
            seg.set_shard_slot(slot)
            load[slot] += 1

    # ------------------------------------------------------------- replicas
    def clone(self) -> "ShardedQueryEngine":
        """Serving replica on the same mesh: segments keep their shard
        slots (stable, stored on the sketch) and their uploaded
        per-shard rows, so a replica costs only fresh jit caches."""
        return ShardedQueryEngine(self.segments, mesh=self.mesh,
                                  shard_axes=self.shard_axes,
                                  n_postings=self.n_postings,
                                  lru_lists=self._lru_cap,
                                  extract_on_device=self._extract_on_device)

    # -------------------------------------------------------------- buckets
    def _seg_pad_key(self, seg) -> tuple:
        lb, lo = seg._level_layout()
        return (lb, lo, seg.sig_bits,
                _p2(seg.signatures.size),
                _p2(seg.mphf.fallback_fps.size),
                _p2(seg.csf.bitseq.size), _p2(seg.csf.lengths.size),
                _p2(seg.csf.samples.size),
                _p2(seg.planes.shape[0]), self.words)

    def _build_buckets(self) -> list[tuple[tuple, list[int]]]:
        buckets: dict[tuple, list[int]] = {}
        for si, seg in self._plane_segs:
            buckets.setdefault(self._seg_pad_key(seg), []).append(si)
        return sorted(buckets.items(), key=lambda kv: kv[1][0])

    def _seg_row_host(self, seg, key) -> dict:
        """The segment's padded flat buffers (host), per its pad key."""
        (_, _, _, sig_p2, fb_p2, bs_p2, ln_p2, sm_p2, pl_p2, w) = key
        m, c = seg.mphf, seg.csf
        planes = np.zeros((pl_p2, w), np.uint32)
        pw = min(seg.planes.shape[1], w)
        planes[:seg.planes.shape[0], :pw] = seg.planes[:, :pw]
        return {
            "words": m.words, "block_rank": m.block_rank,
            "fallback_fps": _pad1(m.fallback_fps, fb_p2, 0xFFFFFFFF),
            "fallback_idx": _pad1(m.fallback_idx.astype(np.int32), fb_p2),
            "fb_count": np.int32(m.fallback_fps.size),
            "signatures": _pad1(seg.signatures, sig_p2),
            "n_tokens1": np.int32(max(seg.n_tokens - 1, 0)),
            "csf_bitseq": _pad1(c.bitseq, bs_p2),
            "csf_lengths": _pad1(c.lengths, ln_p2),
            "csf_samples": _pad1(c.samples.astype(np.int32), sm_p2),
            "planes": planes,
            "n_lists1": np.int32(max(seg.n_lists - 1, 0)),
            "active": np.int32(1),
        }

    def _zero_row(self, key) -> dict:
        """An all-zero padded row: probes to absent everywhere, so it is
        the identity of the per-shard OR (used to even out shard loads)."""
        (_, lo, _, sig_p2, fb_p2, bs_p2, ln_p2, sm_p2, pl_p2, w) = key
        n_words = _row_n_words(lo)
        return {
            "words": np.zeros(n_words, np.uint32),
            "block_rank": np.zeros((n_words + 7) // 8, np.uint32),
            "fallback_fps": np.full(fb_p2, 0xFFFFFFFF, np.uint32),
            "fallback_idx": np.zeros(fb_p2, np.int32),
            "fb_count": np.int32(0),
            "signatures": np.zeros(sig_p2, np.uint32),
            "n_tokens1": np.int32(0),
            "csf_bitseq": np.zeros(bs_p2, np.uint32),
            "csf_lengths": np.zeros(ln_p2, np.uint32),
            "csf_samples": np.zeros(sm_p2, np.int32),
            "planes": np.zeros((pl_p2, w), np.uint32),
            "n_lists1": np.int32(0),
            "active": np.int32(0),
        }

    # ------------------------------------------------------- stacked arrays
    def _bucket_global(self, key, seg_ids) -> tuple[dict, int]:
        """Assemble (and memoize) the bucket's stacked global arrays:
        (n_shards * s_local, ...) device arrays sharded over the segment
        axis.  Stacking copies the cached rows device-locally (no host
        transfer); the rows stay cached so engine rebuilds after
        compaction re-upload nothing for surviving segments — the index
        (~1% of data, §6) is held twice on device to buy that."""
        hit = self._bucket_arrs.get(key)
        if hit is not None:
            return hit
        by_shard: list[list] = [[] for _ in range(self.n_shards)]
        for si in seg_ids:
            seg = self.segments[si]
            by_shard[seg.get_shard_slot()].append(seg)
        s_local = max(1, max(len(g) for g in by_shard))

        # one row dict per (shard, local slot, replica device)
        zero_host = None
        names = list(_ROW_FILL) + ["planes"] + list(_ROW_SCALARS)
        blocks: dict[str, list] = {n: [] for n in names}
        for shard, group in enumerate(by_shard):
            for dev in self._shard_devices[shard]:
                rows = []
                for seg in group:
                    arrs, uploaded = seg.device_row_cache(
                        key, dev,
                        lambda s=seg: self._seg_row_host(s, key))
                    if uploaded:
                        self.upload_count += 1
                    rows.append(arrs)
                if len(rows) < s_local:
                    if zero_host is None:
                        zero_host = self._zero_row(key)
                    zrow = {n: jax.device_put(v, dev)
                            for n, v in zero_host.items()}
                    rows.extend([zrow] * (s_local - len(rows)))
                for n in names:
                    blocks[n].append(jnp.stack([r[n] for r in rows]))

        garrs = {}
        for n in names:
            blk = blocks[n][0]
            gshape = (self.n_shards * s_local,) + blk.shape[1:]
            spec = P(self.shard_axes, *([None] * (blk.ndim - 1)))
            garrs[n] = jax.make_array_from_single_device_arrays(
                gshape, NamedSharding(self.mesh, spec), blocks[n])
        self._bucket_arrs[key] = (garrs, s_local)
        return garrs, s_local

    # ------------------------------------------------------------- dispatch
    def _wave_fn(self, bucket_arrs: list[tuple]):
        """ONE jitted shard_map per wave, covering every bucket: each
        shard runs the SAME per-segment ``match_bitmap_from`` probe the
        single-device engine jits against its local segments of ALL
        level-layout buckets, OR-folds the token planes locally, and the
        per-shard partials merge in a single all-gather + OR — one
        dispatch and one collective per wave, independent of the segment
        fleet size."""
        fn = self._wave_fn_cached
        if fn is None:
            metas = [(key[0], key[1], key[2], s_local)
                     for (key, _), (_, s_local)
                     in zip(self._buckets, bucket_arrs)]
            out_w = self.words
            n_shards = self.n_shards
            axis = (self.shard_axes if len(self.shard_axes) > 1
                    else self.shard_axes[0])
            names = list(_ROW_FILL) + ["planes"] + list(_ROW_SCALARS)
            row_specs = {n: P(self.shard_axes) for n in _ROW_SCALARS}
            row_specs.update({n: P(self.shard_axes, None)
                              for n in _ROW_FILL})
            row_specs["planes"] = P(self.shard_axes, None, None)

            def copr_probe(fps2d, garrs_list):
                self.compile_count += 1          # runs once per trace
                q, t = fps2d.shape
                flat = fps2d.reshape(-1)
                acc = jnp.zeros((q * t, out_w), jnp.uint32)
                for (lb, lo, sig_bits, s_local), arrs \
                        in zip(metas, garrs_list):

                    def probe(row, lb=lb, lo=lo, sig_bits=sig_bits):
                        return match_bitmap_from(
                            flat, row, level_bits=lb,
                            level_word_offset=lo, sig_bits=sig_bits)

                    for i in range(s_local):
                        row = {n: arrs[n][i] for n in names}
                        # zero-padded slots (shard-load evening) skip
                        # the probe at runtime — a shard only pays for
                        # the segments it actually owns
                        acc = acc | jax.lax.cond(
                            row["active"] > 0, probe,
                            lambda _row: jnp.zeros((q * t, out_w),
                                                   jnp.uint32),
                            row)
                # the one cross-shard exchange of the wave: all-gather
                # the partial bitmaps, OR-fold locally (far cheaper than
                # gathering shard-by-shard outside the shard_map)
                if n_shards > 1:
                    parts = jax.lax.all_gather(acc, axis)
                    parts = parts.reshape(n_shards, q * t, out_w)
                    for k in range(n_shards):
                        acc = parts[k] if k == 0 else acc | parts[k]
                return acc.reshape(q, t, out_w)

            smapped = jax.shard_map(
                copr_probe, mesh=self.mesh,
                in_specs=(P(None, None),
                          tuple(row_specs for _ in metas)),
                out_specs=P(None, None, None), check_vma=False)
            fn = self._wave_fn_cached = jax.jit(smapped)
        return fn

    def _device_token_planes(self, fps_dev):
        """Sharded override of the engine's plane fan-out: one fused
        dispatch for the whole bucketed fleet instead of one per
        segment.  The merged planes come out replicated over the mesh;
        the reduce and extract stages run once, on the first shard's
        device, where a replica already sits."""
        if not self._buckets:
            return None
        bucket_arrs = [self._bucket_global(key, seg_ids)
                       for key, seg_ids in self._buckets]
        fn = self._wave_fn(bucket_arrs)
        planes = fn(fps_dev, tuple(g for g, _ in bucket_arrs))
        return jax.device_put(planes, self._shard_devices[0][0])


def _row_n_words(level_word_offset: tuple) -> int:
    """Word count of a level layout's concatenated bit-vectors (matches
    ``build_mphf``'s rank-block padding)."""
    from .mphf import RANK_BLOCK_WORDS
    n = int(level_word_offset[-1]) if level_word_offset else 0
    if n == 0:
        return RANK_BLOCK_WORDS
    return n + ((-n) % RANK_BLOCK_WORDS)
