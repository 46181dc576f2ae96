"""The guarantees a configuration states, checked on the timed jobs' lane
states with plain numpy, from the configuration and the seed's streams
alone.

This check shares no code with the simulator or with its regression copy
(``bench.reference``): it knows the deployment as the configuration file
describes it (tables, widths, 512 KiB pages of uniform tuples) and the
query plan the generator dealt to each stream slot, and asks what any
correct run to the horizon must show.  Each number is the largest breach
over the lanes, negative where every lane keeps the guarantee with room
to spare: a sound run reads 0 or less on every one.

* ``short_lanes``: lanes whose clock stopped before the horizon while a
  stream still had queries to run.
* ``cpu_excess``: the largest share by which a stream's work at its
  queries' CPU rates (the queries it finished, whole, and the tuples of
  the current one) exceeds the lane's clock: no scan runs faster than
  its rate.
* ``io_excess``: the share by which a lane's loaded bytes exceed its
  bandwidth times its clock, one page of banked credit allowed: the I/O
  server serves at most its bandwidth.
* ``unloaded_pages``: pages that the lane's finished queries covered, on
  their columns, beyond the pages the lane loaded: the pool starts cold,
  so each must have been loaded at least once.
* ``pool_excess_pages``: resident pages beyond what the pool holds, with
  one short page per column (the tail) allowed.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

#: the limit of each number: a sound run reads 0, up to float32 rounding
#: of the clock and the cursors for the two shares
LIMITS: Dict[str, float] = {
    "short_lanes": 0.0,
    "cpu_excess": 1e-5,
    "io_excess": 1e-5,
    "unloaded_pages": 0.0,
    "pool_excess_pages": 0.0,
}


def page_ranges(n_tuples: int, width: float, page_bytes: int) -> np.ndarray:
    """First tuple of each page of one column, and the end: pages split
    the column's bytes at ``page_bytes`` with uniform tuples per page."""
    total = int(np.ceil(n_tuples * width))
    n = max(1, -(-total // page_bytes))
    return (n_tuples * np.arange(n + 1, dtype=np.int64)) // n


class Plan:
    """The deployment's pages and each stream slot's queries."""

    def __init__(self, config: dict, streams: List[list], tuples: Dict[str, int]):
        self.page_bytes = config["page_bytes"]
        self.bounds = {}
        for tname, t in config["tables"].items():
            for col, width in t["columns"].items():
                self.bounds[(tname, col)] = page_ranges(
                    tuples[tname], width, self.page_bytes)
        self.n_columns = len(self.bounds)
        # per slot: (table, columns, first, last, rate) of each query
        self.queries = [[(q.table, tuple(q.columns), q.ranges[0][0],
                          q.ranges[0][1], float(q.tuple_rate)) for q in s]
                        for s in streams]

    def covered(self, table, col, first, last) -> range:
        """Indices of the column's pages overlapping ``[first, last)``."""
        b = self.bounds[(table, col)]
        lo = int(np.searchsorted(b, first, side="right")) - 1
        hi = int(np.searchsorted(b, last, side="left"))
        return range(max(lo, 0), min(hi, len(b) - 1))


def numbers(job: Dict[str, np.ndarray], plan: Plan, capacities, bandwidth,
            horizon: float) -> Dict[str, float]:
    """Each guarantee's breach over the lanes of one job."""
    t = np.asarray(job["t"], np.float64)
    qidx = np.asarray(job["qidx"])
    pos = np.asarray(job["pos"], np.float64)
    n_q = np.array([len(s) for s in plan.queries])
    out = dict.fromkeys(LIMITS, -np.inf)
    out["short_lanes"] = 0.0
    for i in range(len(t)):
        running = bool(np.any(qidx[i] < n_q))
        if running and t[i] < horizon * (1 - 1e-6):
            out["short_lanes"] += 1
        need = set()
        for s, queries in enumerate(plan.queries):
            k = int(qidx[i, s])
            work = sum((last - first) / rate
                       for _, _, first, last, rate in queries[:k])
            if k < len(queries):
                work += pos[i, s] / queries[k][4]
            out["cpu_excess"] = max(out["cpu_excess"],
                                    (work - t[i]) / max(t[i], 1e-9))
            for table, cols, first, last, _ in queries[:k]:
                for c in cols:
                    need.update((table, c, p)
                                for p in plan.covered(table, c, first, last))
        allowed = bandwidth * t[i] + plan.page_bytes
        out["io_excess"] = max(out["io_excess"],
                               float(job["io_bytes"][i]) / allowed - 1.0)
        out["unloaded_pages"] = max(out["unloaded_pages"],
                                    float(len(need) - int(job["loads"][i])))
        held = int(capacities[i]) // plan.page_bytes + plan.n_columns
        out["pool_excess_pages"] = max(
            out["pool_excess_pages"],
            float(int(np.sum(job["resident"][i])) - held))
    return out


