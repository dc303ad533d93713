"""What the drivers ask of the system under test alike: the tuning database a
cell names and the dispatch tiers a runtime served. What depends on the model
family (the check that the program's model is the configuration file's, the
reference, the operation counts) is the family module's."""
from __future__ import annotations

import os


def tuning_db(run):
    """The database the cell names (a path under the benchmark's directory),
    or an empty one: every site then resolves at the heuristic tier."""
    from repro.core.database import TuningDatabase

    path = run.cell.get("tuning_db")
    return TuningDatabase(os.path.join(run.bench, path) if path else None)


def tiers(rt):
    """{tier: dispatches} over every site ``rt`` resolved (at trace time)."""
    out = {}
    for per in rt.telemetry.snapshot()["by_key"].values():
        for tier, n in per.items():
            out[tier] = out.get(tier, 0) + n
    return out
