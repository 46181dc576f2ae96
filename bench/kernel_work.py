"""The work one call of each substrate op must do, from its shapes alone.

A call serves ``lanes`` lanes of ``pages`` pages each.  Per lane it must
read the op's key, size and flag arrays and write its mask or counts
once; its operations are the selection of the service order over the
pages (one comparison a page for a top-k pick, ``log2(pages)`` a page for
the wake solve's full order) and one running byte sum.  The count is the
same whichever implementation sits behind the op's scope (the O(P^2)
Mosaic kernel, an O(P) kernel or the jnp form), so a rewrite is measured
against the same work.
"""

from __future__ import annotations

import math
from typing import Dict

F32 = I32 = 4
BOOL = 1
SCALAR = 4

#: per lane: (bytes per page, fixed bytes) read and written
_BYTES = {
    # key, sizes, evictable in; need_free in; evict mask out
    "batched_evict": (F32 + F32 + BOOL + BOOL, SCALAR),
    # key, sizes in; budget, pops in; grant mask out; bytes, count out
    "fifo_grant": (I32 + F32 + BOOL, 4 * SCALAR),
    # key, sizes in; credit0, inc, pops in; grant step out
    "wake_solve": (I32 + F32 + I32, 3 * SCALAR),
}
OPS = tuple(_BYTES)


def work(op: str, lanes: int, pages: int) -> Dict[str, float]:
    """``{"bytes": ..., "ops": ...}`` that one call of ``op`` must move and
    compute."""
    per_page, fixed = _BYTES[op]
    order = math.log2(pages) if op == "wake_solve" else 1.0
    return {
        "bytes": float(lanes * (per_page * pages + fixed)),
        "ops": float(lanes * pages * (order + 1.0)),
    }


def least_seconds(op: str, lanes: int, pages: int,
                  peak: Dict[str, float]) -> float:
    """The least time a call can take at the chip's peaks: the larger of
    its bytes over the HBM bandwidth and its operations over the peak
    rate."""
    w = work(op, lanes, pages)
    return max(w["bytes"] / peak["hbm_bytes_per_s"],
               w["ops"] / peak["flops_per_s"])
