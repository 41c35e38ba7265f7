"""Public wrapper for the EmbeddingBag kernel."""
from __future__ import annotations


from .kernel import embedding_bag_pallas
from .ref import embedding_bag_ref  # noqa: F401


def embedding_bag_sum(table, idx):
    """(V, D) table, (B, BAG) int32 -> (B, D) bag sums (Pallas)."""
    return embedding_bag_pallas(table, idx)
