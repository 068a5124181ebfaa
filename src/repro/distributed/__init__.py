"""Sharded multi-device discovery engine (DESIGN.md §11)."""
from .sharded_engine import ShardedEngine, ShardedEngineState

__all__ = ["ShardedEngine", "ShardedEngineState"]
