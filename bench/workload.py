"""The one generator every cell's inputs come from.

It reads a configuration (the tables, their widths and the queries)
and a traffic mix (streams, bandwidth, lanes, horizon), draws the mix's
stream set from the mix's own ``stream_seed`` with the
:mod:`bench.workgen` generator the configuration names, and sizes each
lane's pool as the repository's sweeps size it: a fraction of the bytes
the streams access, and never under 4 MiB.  The run's ``--seed`` then
deals the streams of that set to the stream slots in an order of its
own: every seed runs the same set of scans, sizes and bytes, in another
order, so a seed changes the inputs and not the amount of work.
"""

from __future__ import annotations

import dataclasses
import random
from dataclasses import dataclass
from typing import List

from . import workgen

MIN_POOL = 1 << 22


@dataclass
class Workload:
    db: object
    streams: list
    accessed_bytes: int
    lanes: List[tuple]        # (policy, pool fraction, pool bytes)


def build(config: dict, traffic: dict, seed: int,
          side: str = workgen.PROGRAM) -> Workload:
    """The database, the seed's streams and the lanes, built from the
    classes of ``side`` (the program's or the reference's)."""
    draw, reckon = workgen.GENERATORS[config["generator"]]
    lib = workgen.lib_for(side)
    db = workgen.make_db(lib, config)
    streams = draw(lib, db, config, traffic["n_streams"],
                   traffic["queries_per_stream"], traffic["stream_seed"])
    accessed = reckon(db, config, streams)
    streams = deal(streams, seed)
    lanes = [(pol, frac, max(MIN_POOL, int(frac * accessed)))
             for frac in traffic["pool_fractions"]
             for pol in traffic["policies"]]
    return Workload(db, streams, accessed, lanes)


def deal(streams: list, seed: int) -> list:
    """The streams in the seed's order, each scan relabelled with its new
    stream slot."""
    order = list(range(len(streams)))
    random.Random(seed).shuffle(order)
    return [[dataclasses.replace(scan, stream=slot) for scan in streams[j]]
            for slot, j in enumerate(order)]
