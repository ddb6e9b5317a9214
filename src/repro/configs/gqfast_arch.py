"""The paper's own workload as a dry-run architecture: relationship queries on
PubMed-M-scale data (Table 1: DT 901M rows, DA 61M rows, 23.3M docs, 27.9k MeSH
terms, 6.3M authors) executed by the distributed frontier engine on the
production mesh — edges sharded over (data, model), one psum per hop.

Cells carry full-scale ShapeDtypeStruct edge/attr trees; the chain plan is
built from a tiny same-schema instance (plans depend on the schema + domain
sizes, not on edge values)."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import executor as X
from ..core.engine import GQFastDatabase
from ..core.planner import plan_query
from ..core.sql import parse
from ..data import synth_graph as SG
from .base import ArchConfig, Cell

# PubMed-M full-scale statistics (paper Table 1)
FULL = SG.PUBMED_TABLE1

EDGE_AXES = ("data", "model")


def _pad(n: int, shards: int) -> int:
    return -(-n // shards) * shards


GQFAST_SHAPES = {
    "as_b1": dict(query="AS", batch=0),
    "as_b8": dict(query="AS", batch=8),
    "ad_b8": dict(query="AD", batch=8),
    "fad_b8": dict(query="FAD", batch=8),
}

_QUERIES = {"AS": SG.QUERY_AS, "AD": SG.QUERY_AD, "FAD": SG.QUERY_FAD}


class GQFastArch(ArchConfig):
    kind = "gqfast"
    shape_ids = list(GQFAST_SHAPES)

    def __init__(self):
        self.arch_id = "gqfast-pubmed"
        self._tiny = None

    def _tiny_db(self) -> GQFastDatabase:
        if self._tiny is None:
            # tiny edge sets, FULL entity domain sizes (plans bake domain sizes)
            schema = SG.make_pubmed(
                n_docs=FULL["n_docs"], n_terms=FULL["n_terms"],
                n_authors=FULL["n_authors"],
                avg_terms_per_doc=3e-4, avg_authors_per_doc=1e-4, seed=0,
            )
            self._tiny = GQFastDatabase(schema, account_space=False)
        return self._tiny

    def make_cell(self, shape_id: str, mesh, variant: str = "") -> Cell:
        sh = GQFAST_SHAPES[shape_id]
        db = self._tiny_db()
        plan = plan_query(db.schema, parse(_QUERIES[sh["query"]]))
        batched = sh["batch"] > 0
        axes = ("data",) if variant == "data_only" else EDGE_AXES
        fdt = jnp.bfloat16 if variant == "bf16_frontier" else jnp.float32
        call = X.compile_frontier_distributed(
            db.device, plan, mesh, axes, batched=batched, frontier_dtype=fdt
        )
        jitted, edge_tree, side_tree, edge_specs, side_specs = call.lowerable
        nshards = int(np.prod([mesh.shape[a] for a in axes]))

        # full-scale abstract trees with the same structure
        def edge_abs(key: str, leafname: str, leaf):
            table = key.split("::")[0]
            E = _pad(FULL["dt_edges" if table == "DT" else "da_edges"], nshards)
            return jax.ShapeDtypeStruct((E,), leaf.dtype)

        edges_abs = {
            k: {n: edge_abs(k, n, v) for n, v in sub.items()}
            for k, sub in edge_tree.items()
        }
        side_abs = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), side_tree
        )
        names = X.collect_params(plan)
        if batched:
            p_abs = tuple(jax.ShapeDtypeStruct((sh["batch"],), jnp.int32) for _ in names)
        else:
            p_abs = tuple(jax.ShapeDtypeStruct((), jnp.int32) for _ in names)

        edge_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), edge_specs,
                               is_leaf=lambda x: isinstance(x, P))
        side_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), side_specs,
                               is_leaf=lambda x: isinstance(x, P))
        p_sh = tuple(NamedSharding(mesh, P()) for _ in names)

        def fn(edges, side, *params):
            return jitted.__wrapped__(edges, side, *params) if hasattr(jitted, "__wrapped__") else jitted(edges, side, *params)

        # total work ≈ 2 flops/edge/hop over touched edge space; report the
        # dense-equivalent convention: 6·(edges)·(batch or 1)
        b = max(sh["batch"], 1)
        mf = 2.0 * (FULL["dt_edges"] * 2 + FULL["da_edges"] * 2) * b
        return Cell(self.arch_id, shape_id, fn, (edges_abs, side_abs) + p_abs,
                    (edge_sh, side_sh) + p_sh, None, "serve", mf,
                    notes=f"query={sh['query']} frontier-SpMV chain")

    def smoke(self) -> dict:
        schema = SG.make_pubmed(n_docs=500, n_terms=50, n_authors=200)
        db = GQFastDatabase(schema, account_space=False)
        from ..core.engine import GQFastEngine
        from ..core.reference import run_sql

        eng = GQFastEngine(db)
        got = eng.query(SG.QUERY_AS, a0=7)
        ref = run_sql(schema, SG.QUERY_AS, {"a0": 7})
        return {
            "match": bool(np.allclose(got, ref, rtol=1e-4, atol=1e-4)),
            "nnz": int((got != 0).sum()),
            "finite": bool(np.isfinite(got).all()),
        }


GQFAST = GQFastArch()
