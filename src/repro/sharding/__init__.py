from jax import shard_map
from .partition import (
    LOGICAL_RULES,
    batch_shardings,
    cache_shardings,
    data_axes,
    param_shardings,
    resolve_spec,
)

__all__ = [
    "shard_map",
    "LOGICAL_RULES",
    "batch_shardings",
    "cache_shardings",
    "data_axes",
    "param_shardings",
    "resolve_spec",
]
