"""Parallelism substrate: sharding rules + step builders."""
from .sharding import BASE_RULES, RULE_VARIANTS, Sharder, make_rules

__all__ = ["BASE_RULES", "RULE_VARIANTS", "Sharder", "make_rules"]
