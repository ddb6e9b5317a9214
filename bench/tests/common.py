"""What the self-tests share."""
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

#: A small deployment of each schema, for tests on the CPU.
TINY = {
    "pubmed": dict(n_docs=3000, n_terms=400, n_authors=800, dt_rows=24000,
                   da_rows=9000),
}
