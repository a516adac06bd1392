"""Clustered candidate-generation: sublinear two-stage search on both axes.

``ClusteredIndex`` partitions *users* with blocked spill k-means, then
answers neighbor queries by probing the nearest clusters and *exactly*
reranking only their members — true similarity scores at sublinear
candidate-generation cost; it checkpoints through
``state()``/``load_state()``.  ``ItemClusteredIndex`` is the stateless
recommend stage: the exact predictor's support scores over every item, a
device shortlist, an exact rerank.  ``CFEngine(neighbor_mode="approx")``
/ ``CFEngine(recommend_mode="approx")`` are the integrated entry points.
"""

from repro.index.clustered import (ClusteredIndex, IndexConfig, QueryStats,
                                   RefoldStats)
from repro.index.item_index import (ItemClusteredIndex, ItemIndexConfig,
                                    RecommendStats)
from repro.index.kmeans import KMeansStats, center_rows, kmeans

__all__ = ["ClusteredIndex", "IndexConfig", "ItemClusteredIndex",
           "ItemIndexConfig", "KMeansStats", "QueryStats", "RecommendStats",
           "RefoldStats", "center_rows", "kmeans"]
