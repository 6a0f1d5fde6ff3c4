"""Parallelism plane: device meshes, partition rules, compile dispatch.

- :mod:`fedml_tpu.parallel.mesh` — mesh constructors (clients / silo /
  clients x model) and sharding helpers.
- :mod:`fedml_tpu.parallel.rules` — regex partition rules -> PartitionSpec
  plans for model + optimizer pytrees (docs/PERFORMANCE.md "Sharded client
  models").
- :mod:`fedml_tpu.parallel.dispatch` — pjit-when-sharded /
  shard_map-when-mapped compile dispatcher.
"""

from fedml_tpu.parallel.dispatch import lower, plan_is_sharded  # noqa: F401
from fedml_tpu.parallel.mesh import (  # noqa: F401
    CLIENT_AXIS,
    MODEL_AXIS,
    SILO_AXIS,
    client_mesh,
    named_sharding,
    shard_mesh,
    silo_mesh,
)
from fedml_tpu.parallel.rules import (  # noqa: F401
    RULE_SETS,
    RuleSet,
    match_partition_rules,
    rule_set,
)
