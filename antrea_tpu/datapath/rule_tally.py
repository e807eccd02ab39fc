"""Per-rule NetworkPolicy packet and byte counters of one direction (the
IngressMetric / EgressMetric analog), kept as arrays indexed by rule.

A step's lanes carry rule INDICES into the compiled direction's
`rule_ids`.  The tally adds each step's two `bincount`s to int64 arrays
bound to that list object, so no step walks the rules it named.  The
arrays are folded into totals keyed by rule id (which survive bundle
renumbering) only when a step counts under another list, and whenever
the counters are read.  A list is never edited once compiled: compile,
rollback and tenant swaps replace the compiled set, so a new list object
is the one sign that the numbering changed."""

from __future__ import annotations

import numpy as np

_EMPTY = np.zeros(0, np.int64)


def _fold(packets: dict, volumes: dict, ids, pk: np.ndarray,
          by: np.ndarray) -> tuple[dict, dict]:
    """Add the arrays `pk` / `by` (indexed like `ids`) into the id-keyed
    `packets` / `volumes` in place: empty ids are skipped, indices that
    share an id sum, and a byte key appears only for a non-zero volume."""
    hit = np.nonzero(pk)[0]
    for r, n, v in zip(hit.tolist(), pk[hit].tolist(), by[hit].tolist()):
        rid = ids[r]
        if rid:
            packets[rid] = packets.get(rid, 0) + n
            if v:
                volumes[rid] = volumes.get(rid, 0) + v
    return packets, volumes


class RuleTally:
    """One direction's per-rule packet and byte counts."""

    __slots__ = ("_live",)

    def __init__(self) -> None:
        # (packet totals, byte totals, bound rule_ids, packet array, byte
        # array), replaced whole and never edited but for the arrays'
        # `+=`: a reader on another thread (the agent API) folds one
        # numbering's totals with that numbering's own arrays.
        self._live = ({}, {}, None, _EMPTY, _EMPTY)

    def add(self, ids: list, vals: np.ndarray, lens=None) -> None:
        """Count one packet per entry of `vals` (indices into `ids`, all
        in range) and, given `lens`, its length in bytes."""
        packets, volumes, bound, pk, by = self._live
        if ids is not bound:
            packets, volumes = _fold(dict(packets), dict(volumes), bound,
                                     pk, by)
            pk = np.zeros(len(ids), np.int64)
            by = np.zeros(len(ids), np.int64)
            self._live = (packets, volumes, ids, pk, by)
        pk += np.bincount(vals, minlength=len(ids))
        if lens is not None:
            # Exact: a step's per-rule byte sum stays far below 2**53.
            by += np.bincount(vals, weights=lens,
                              minlength=len(ids)).astype(np.int64)

    def read(self) -> tuple[dict, dict]:
        """(packets by rule id, bytes by rule id); leaves the tally as
        it is."""
        packets, volumes, ids, pk, by = self._live
        return _fold(dict(packets), dict(volumes), ids, pk, by)
