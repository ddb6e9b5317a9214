"""Frozen copies of the data generators the benchmark's deployments are made
from. They return a plain :class:`Dataset`; the harness turns it into the
program's schema, and the reference and the bytes count read it as it is."""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Relationship:
    """A relationship table: two foreign keys and any measure columns."""

    keys: tuple[str, str]
    entities: tuple[str, str]
    columns: dict[str, np.ndarray]

    def entity_of(self, key: str) -> str:
        return self.entities[self.keys.index(key)]

    def other(self, key: str) -> str:
        return self.keys[1 - self.keys.index(key)]

    @property
    def measures(self) -> list[str]:
        return [c for c in self.columns if c not in self.keys]


@dataclass
class Dataset:
    """Entity domains (size and attribute columns) and relationship tables."""

    sizes: dict[str, int]
    attributes: dict[str, dict[str, np.ndarray]] = field(default_factory=dict)
    relationships: dict[str, Relationship] = field(default_factory=dict)


def generate(schema: str, cfg: dict, seed: int) -> Dataset:
    """The deployment of configuration ``cfg`` from ``bench/data/<schema>.py``."""
    return importlib.import_module(f"bench.data.{schema}").generate(cfg, seed)
