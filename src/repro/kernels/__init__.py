"""Pallas TPU kernels for the perf-critical paths.

Paper hot spots (DynaWarp):
  token_hash      — ingest-side batched token fingerprinting
  bitset_ops      — posting-plane AND/OR + popcount (Alg. 3 consumer)
  bitmap_extract  — hit bitmap -> compacted posting-id lists (device-side
                    candidate extraction; only (Q, max_hits) ids cross
                    back to the host)
  csc_probe       — CSC baseline probe (fair sketch-vs-sketch comparison)
Framework hot spots (assigned archs):
  embedding_bag   — recsys fixed-bag lookup+reduce (scalar prefetch)
  retrieval_score — 1M-candidate corpus GEMV (two-tower retrieval_cand)
  flash_decode    — one-token GQA attention vs long KV caches
                    (decode_32k / long_500k serving path)

The immutable-sketch MPHF probe is not a kernel: its per-query random
word lookups are gathers over a multi-MB word array, which Mosaic cannot
lower (it gathers only within a tile) and XLA lowers natively
(``core.mphf.lookup_arrs``).

Every kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py
(wrapper: padding to the kernel's tiles) and ref.py (pure-jnp oracle);
``backend.interpret_mode`` decides once whether ``pallas_call``
interprets (off the TPU) or compiles (on it).  Tests sweep shapes and
dtypes and assert kernel-vs-oracle equality.
"""
from .bitmap_extract.ops import bitmap_extract
from .bitset_ops.ops import bitset_reduce, bitset_reduce_batch
from .csc_probe.ops import csc_partition_mask
from .embedding_bag.ops import embedding_bag_sum
from .flash_decode.ops import flash_decode
from .retrieval_score.ops import retrieval_scores, retrieval_topk
from .token_hash.ops import token_fingerprints

__all__ = ["bitmap_extract", "bitset_reduce", "bitset_reduce_batch",
           "csc_partition_mask",
           "embedding_bag_sum", "flash_decode",
           "retrieval_scores", "retrieval_topk", "token_fingerprints"]
