"""Distributed subsystem: sharding plans + mesh-sharded ANN search.

``sharding``   — ShardPlan role->axis resolution and the builders of
                 the sharded brute/lexical/hybrid/IVF/forest scans
                 (corpus over one mesh axis set, queries optionally
                 over another).
``backend``    — places a corpus or index once and serves the builders'
                 jitted callables; plugs into ``serve.cell.ServingCell``
                 as ``search_fn``.

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
    slice_forest_delta,
    slice_ivf_delta,
)

__all__ = [
    "ShardPlan", "SINGLE_POD_PLAN", "MULTI_POD_PLAN", "LOCAL_PLAN",
    "make_sharded_brute_fn", "make_sharded_ivf_fn", "make_sharded_forest_fn",
    "shard_forest", "forest_shard_shapes", "ForestShardShapes",
    "slice_forest_delta", "slice_ivf_delta",
    "ShardedSearchBackend",
]
