"""Graph analytics (≙ reference ``ml/graph/``): adjacency spectral
embedding and seed-set local community detection."""

from .ase import ASEParams, approximate_ase
from .community import find_local_cluster, time_dependent_ppr
from .graph import SimpleGraph, adjacency_from_edges, read_arc_list
from .stream import (
    adjacency_sketch_fold,
    ase_from_sketch,
    chained_adjacency_sketch,
    graph_block_source,
    incore_adjacency_sketch,
    streamed_adjacency_sketch,
    streaming_ase,
)

__all__ = [
    "SimpleGraph",
    "read_arc_list",
    "adjacency_from_edges",
    "ASEParams",
    "approximate_ase",
    "time_dependent_ppr",
    "find_local_cluster",
    "graph_block_source",
    "adjacency_sketch_fold",
    "incore_adjacency_sketch",
    "streamed_adjacency_sketch",
    "chained_adjacency_sketch",
    "ase_from_sketch",
    "streaming_ase",
]
