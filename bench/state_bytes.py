"""The least bytes one simulator step must move: the fluid state read
once and written once, at the configuration's precision.

The state is fixed by the deployment's sizes, not by how a step is
implemented: N routers, K out-slots (the maximum degree), M active
routers (the mid axis of the first Valiant leg) and C demanded
destination columns (M when the demand reaches every router).

    q0, q2 : N * K * C     (minimal queues, second Valiant leg)
    q1     : N * K * M     (first Valiant leg, addressed to the mids)
    src    : N * C         (source backlog)
    pend   : M * C         (phase-1 (mid, dest) pool)

Route tables are left out on purpose, so that compressing them, fusing
the virtual channels or moving a gather into a kernel can never push a
roofline share built on this count past 100 %.
"""

from __future__ import annotations


def state_cells(n: int, k: int, m: int, c: int) -> dict:
    return {"q0": n * k * c, "q1": n * k * m, "q2": n * k * c,
            "src": n * c, "pend": m * c}


def step_bytes(n: int, k: int, m: int, c: int, itemsize: int) -> int:
    """Read + write of the whole state, in bytes."""
    return 2 * itemsize * sum(state_cells(n, k, m, c).values())
