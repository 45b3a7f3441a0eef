"""Layout plans: what a layout owes to the rows alone is built once for
the batches of a statement that were fed the same rows.

A layout has two halves.  Its PLAN is everything that depends only on
which rows there are — relative times, segment ids, series ids, record
boundaries, the number of segments and the window geometry: the grid's
stride analysis, its refusal, its scatter and combine indexes
(models/grid.py); the buckets' counts, run analysis, sub-row layout and
scatter index, and the time and row-index matrices the selector kernels
read (models/ragged.py).  Its FILL is the scatter of one field's values
and validity mask through the plan's index.

The executor hands the very same `rel`, `seg` and `sids` arrays to every
field of a statement (query/qhelpers.py `_add_record_to_batches`, the
scan stager's flush), and add() keeps what it is handed.  So "the same
rows" is something the code can observe: the same array objects under
the same geometry.  A field missing from some series, a second scan
range or a batch built apart was handed other arrays and plans for
itself through the same code, as a group of one — as a lone launch is in
models/launch.py.

A `Plans` lives as long as its statement: the executor makes one beside
the statement's batches and `pick_batch` hands it to each.  Nothing is
kept across statements, so nothing is ever invalidated.  A plan is
immutable once built: a batch that lets go of its rows (GridBatch
prefetch) drops its own references only.
"""

from __future__ import annotations

import numpy as np


class Plans:
    """One statement's plans, by the row arrays each was built from."""

    def __init__(self):
        self._memo: dict = {}

    def get(self, geometry: tuple, parts, build):
        """(plan, shared).  `parts` are the row arrays (or scalars, or
        None) the plan reads, as add() was handed them; `build()` makes
        the plan where no batch of this geometry was handed the same
        ones before, and `shared` says that one was.  A refusal (None)
        is a plan too: it is decided once."""
        parts = tuple(parts)
        key = geometry + tuple(
            ("is", id(p)) if isinstance(p, np.ndarray) else ("eq", p)
            for p in parts)
        hit = self._memo.get(key)
        if hit is not None:
            return hit[0], True
        plan = build()
        # the parts stay referenced beside the plan: an id is unique only
        # while its object lives
        self._memo[key] = (plan, parts)
        return plan, False


def cat(parts, dtype=None) -> np.ndarray:
    """The parts of one column as one array of `dtype`: the part itself
    where there is one and it has the dtype already (a bulk scan adds
    once), so nobody may write to the result."""
    if len(parts) == 1:
        return np.asarray(parts[0], dtype=dtype)
    return np.concatenate(parts, dtype=dtype)
