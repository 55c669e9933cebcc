"""Parallelism substrate: logical-axis sharding rules, mesh/shard_map
entry points, and spec derivation for params/batches/caches.

``api``      — ``ShardingRules`` (logical axis -> mesh axis), the
               ``use_rules``/``current_rules`` context, and ``constrain``
               (``with_sharding_constraint`` under active rules, identity
               otherwise).
``sharding`` — ``ShardFlags``, ``make_rules`` (train/serve rule sets),
               and the pytree spec derivers ``param_specs`` /
               ``batch_specs`` / ``cache_specs`` / ``to_shardings``.
``compat``   — ``make_mesh`` and ``shard_map``, spelled once for the
               installed JAX.
"""
from . import api, compat, sharding
from .api import ShardingRules, constrain, current_rules, use_rules
from .sharding import (ShardFlags, batch_specs, cache_specs, make_rules,
                       param_specs, to_shardings)

__all__ = [
    "api", "compat", "sharding",
    "ShardingRules", "constrain", "current_rules", "use_rules",
    "ShardFlags", "batch_specs", "cache_specs", "make_rules",
    "param_specs", "to_shardings",
]
