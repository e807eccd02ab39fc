"""The bytes of a step that no implementation can avoid, from shapes alone.

Counted at the width of the information, not of today's arrays, so that a
later kernel or layout change neither makes the count stale nor lifts a
roofline share over 100 %:

  per lane         the 5-tuple the step is handed (5 x 4 B), one flow-cache
                   row read (key 16 B + meta 16 B), the last-seen stamp
                   written (4 B), and what StepResult needs of the verdict
                   (post-DNAT address 4 B; code, Service, port 4 B; the two
                   rule ids 4 B; the marks 4 B)
  per missed lane  the committed cache row written (32 B)

This program is bound by the latency of gathers, not by bandwidth, so its
share of this roofline is far under 1 %; the number says how far, and moves
only when device time moves.
"""

from __future__ import annotations

INGRESS_BYTES = 5 * 4
ROW_READ_BYTES = 16 + 16
STAMP_BYTES = 4
ANSWER_BYTES = 4 * 4
ROW_WRITE_BYTES = 32


def step_bytes(lanes: int, n_miss: int) -> int:
    return (lanes * (INGRESS_BYTES + ROW_READ_BYTES + STAMP_BYTES
                     + ANSWER_BYTES) + n_miss * ROW_WRITE_BYTES)


def least_seconds(lanes: int, n_miss: int, hbm_bytes_per_s: float) -> float:
    """The least time the chip could take: the bytes over the HBM peak (no
    arithmetic in a lookup competes with that bound)."""
    return step_bytes(lanes, n_miss) / hbm_bytes_per_s
