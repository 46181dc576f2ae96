"""The benchmark's traffic generator: range scans over the tables of a
configuration (the paper's microbenchmark, §4.1).

Everything it draws from comes from the configuration file: the tables
with their tuples, chunks and per-column widths (compressed bytes per
tuple), the query kinds with their table, columns, CPU rate and share of
the mix, and the range fractions.  A query picks a range fraction, a
start and a kind from the stream's ``random.Random``, in that order, as
``repro.core.workload.micro_query`` does, so that the same configuration
and seed give the program's own streams.  Each generator takes ``lib``,
the module pair that provides ``Database`` and ``ScanSpec``:
:data:`PROGRAM` builds the program's own objects (the timed path),
:data:`REFERENCE` the regression copy's (``bench.reference``).
"""

from __future__ import annotations

import random
from types import SimpleNamespace
from typing import List


def _program_lib():
    from repro.core import pages, scans

    return SimpleNamespace(Database=pages.Database, ScanSpec=scans.ScanSpec)


def _reference_lib():
    from bench.reference import pages, scans

    return SimpleNamespace(Database=pages.Database, ScanSpec=scans.ScanSpec)


#: which classes a generator builds: the program's or the reference's
PROGRAM = "program"
REFERENCE = "reference"
_LIBS = {PROGRAM: _program_lib, REFERENCE: _reference_lib}


def lib_for(side: str):
    return _LIBS[side]()


def table_tuples(config: dict, table: str) -> int:
    """The table's tuples at the configuration's scale."""
    return max(1, int(round(config["tables"][table]["tuples"]
                            * config["scale"])))


def make_db(lib, config: dict):
    """The configuration's tables, paged at its page size."""
    db = lib.Database()
    for name, t in config["tables"].items():
        n = table_tuples(config, name)
        db.add_table(
            name,
            n_tuples=n,
            columns=dict(t["columns"]),
            chunk_tuples=max(t["min_chunk_tuples"], n // t["chunks"]),
            page_bytes=config["page_bytes"],
        )
    return db


def range_query(lib, db, config: dict, rng: random.Random, stream: int):
    """One query: a kind of the mix over a random range of its table."""
    kinds = config["queries"]
    table = db.tables[kinds[0]["table"]]
    frac = rng.choice(config["range_fractions"])
    length = max(1, int(table.n_tuples * frac))
    start = rng.randrange(0, max(1, table.n_tuples - length + 1))
    u, kind, cum = rng.random(), kinds[-1], 0.0
    for k in kinds:
        cum += k["share"]
        if u < cum:
            kind = k
            break
    return lib.ScanSpec(
        table=kind["table"],
        columns=tuple(kind["columns"]),
        ranges=((start, start + length),),
        tuple_rate=kind["rate"],
        stream=stream,
    )


def streams(lib, db, config: dict, n_streams: int, queries_per_stream: int,
            seed: int) -> List[list]:
    rng = random.Random(seed)
    return [[range_query(lib, db, config, rng, s)
             for _ in range(queries_per_stream)]
            for s in range(n_streams)]


def accessed_bytes(db, config: dict) -> int:
    """The working set: every column some query kind reads, whole."""
    total = 0
    for name in config["tables"]:
        cols = sorted({c for k in config["queries"] if k["table"] == name
                       for c in k["columns"]})
        if cols:
            total += db.tables[name].total_bytes(cols)
    return total
