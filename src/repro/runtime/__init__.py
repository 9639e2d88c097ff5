from .sharding import ShardingPlan  # noqa: F401
