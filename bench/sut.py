"""The system under test: the program's engine, as its analytics server
drives it. Nothing here measures or judges; ``bench/run.py`` does."""
from __future__ import annotations

from .data import Dataset


def program_schema(data: Dataset):
    """The benchmark's dataset as the program's ``Schema``."""
    from repro.core.schema import EntityTable, RelationshipTable, Schema

    return Schema(
        entities={e: EntityTable(e, n, dict(data.attributes.get(e, {})))
                  for e, n in data.sizes.items()},
        relationships={
            name: RelationshipTable(name, *rel.keys, *rel.entities,
                                    dict(rel.columns))
            for name, rel in data.relationships.items()
        },
    )


class System:
    """One database on the device, its engine with the defaults (strategy
    ``frontier``, block skipping and fusion ``auto``), and one prepared
    query per shape. :meth:`execute` is the call the server makes for each
    micro-batch: ``repro.robust.run_batch_with_policy``."""

    def __init__(self, data: Dataset, queries: dict[str, str]):
        import jax

        from repro.core.engine import GQFastDatabase, GQFastEngine
        from repro.obs.metrics import MetricsRegistry
        from repro.robust import RobustPolicy

        self.db = GQFastDatabase(program_schema(data), account_space=False)
        jax.block_until_ready([di.src_ids for di in self.db.device.indexes.values()])
        self.engine = GQFastEngine(self.db)
        self.registry = MetricsRegistry()
        self.policy = RobustPolicy(registry=self.registry)
        self.prepared = {s: self.engine.prepare(sql) for s, sql in queries.items()}

    def execute(self, shape: str, arrays: dict):
        from repro.robust import run_batch_with_policy

        return run_batch_with_policy(self.prepared[shape], arrays, policy=self.policy)
