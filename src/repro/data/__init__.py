from repro.data.graph_sampling import (
    CSRGraph,
    SampledSubgraph,
    random_graph,
    sample_neighbors,
)
from repro.data.loader import BatchLoader, LoaderState
from repro.data.synthetic import (
    SessionDataset,
    SyntheticConfig,
    clustered_catalog,
    clustered_sessions,
    generate_sessions,
    goodreads_like,
    twitch_like,
)

__all__ = [
    "SessionDataset",
    "SyntheticConfig",
    "clustered_catalog",
    "clustered_sessions",
    "generate_sessions",
    "twitch_like",
    "goodreads_like",
    "BatchLoader",
    "LoaderState",
    "CSRGraph",
    "SampledSubgraph",
    "sample_neighbors",
    "random_graph",
]
