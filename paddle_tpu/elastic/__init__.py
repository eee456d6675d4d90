"""Elastic fleet runtime: training that survives worker churn.

The three layers PR 5 (survive the machine) and PR 7 (derived sharding
plans) were missing a host for:

* ``coordinator`` — :class:`FleetCoordinator` / :class:`FleetClient`:
  worker membership with heartbeat leases, dense rank assignment, a
  monotonically increasing **membership generation**, eviction of
  workers that miss heartbeats, and snapshot/recover — on the same
  framed-JSON TCP transport as ``distributed/master.py``.
* ``reshard`` — checkpoint resharding: :class:`ShardedCheckpointManager`
  lays var files out as per-shard dim-0 splits named by the mesh's
  ``ShardingPlan``; :func:`reshard_checkpoint` reassembles and re-splits
  a checkpoint for a new mesh shape; unsupported layouts (tp column
  splits) raise :class:`ReshardError` naming the var — never silent
  replication.
* ``worker`` — :class:`ElasticTrainSession`: a
  ``resilience.TrainSession`` wrapper whose step barrier acts on
  generation changes — finish the step, bank a sync sharded checkpoint
  (chief), tear down and rebuild the executor/mesh at the new world
  size, reshard-restore, continue — with a loss trajectory bit-identical
  to a fresh restore at that world size.

``docs/RESILIENCE.md`` ("Elastic fleet") has the generation protocol,
the reshard rules table and the failure matrix; ``tests/test_elastic.py``
holds the whole loop through a join and an eviction.
"""

from paddle_tpu.elastic import coordinator  # noqa: F401
from paddle_tpu.elastic import reshard  # noqa: F401
from paddle_tpu.elastic import worker  # noqa: F401
from paddle_tpu.elastic.coordinator import (  # noqa: F401
    FleetClient,
    FleetCoordinator,
    FleetEvictedError,
)
from paddle_tpu.elastic.reshard import (  # noqa: F401
    ReshardError,
    ShardedCheckpointManager,
    reshard_checkpoint,
)
from paddle_tpu.elastic.worker import ElasticTrainSession  # noqa: F401
