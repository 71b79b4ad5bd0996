"""Distributed subsystem: sharding plans + mesh-sharded ANN search.

``sharding``   — ShardPlan role->axis resolution and the sharded
                 brute/IVF/forest search (corpus over one mesh axis set,
                 queries optionally over another).
``backend``    — pre-placed search callables that plug into
                 ``serve.engine.ServingEngine`` as ``search_fn``.

All collectives are built with ``jax.shard_map``.
"""
from repro.distributed.backend import ShardedSearchBackend
from repro.distributed.sharding import (
    LOCAL_PLAN,
    MULTI_POD_PLAN,
    SINGLE_POD_PLAN,
    ForestShardShapes,
    ShardPlan,
    forest_shard_shapes,
    make_sharded_brute_fn,
    make_sharded_forest_fn,
    make_sharded_ivf_fn,
    shard_forest,
    sharded_brute_search,
    sharded_forest_search,
    sharded_ivf_search,
    slice_forest_delta,
    slice_ivf_delta,
)

__all__ = [
    "ShardPlan", "SINGLE_POD_PLAN", "MULTI_POD_PLAN", "LOCAL_PLAN",
    "sharded_brute_search", "sharded_ivf_search", "sharded_forest_search",
    "make_sharded_brute_fn", "make_sharded_ivf_fn", "make_sharded_forest_fn",
    "shard_forest", "forest_shard_shapes", "ForestShardShapes",
    "slice_forest_delta", "slice_ivf_delta",
    "ShardedSearchBackend",
]
