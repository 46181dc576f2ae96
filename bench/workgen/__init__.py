"""The benchmark's traffic generators, one for each value of a
configuration's ``generator`` key (:data:`GENERATORS`).

Everything they draw from comes from the configuration file: the tables
with their tuples, chunks and per-column widths (compressed bytes per
tuple), and the queries.

* ``range_scans`` (the paper's microbenchmark, §4.1): query kinds with
  their table, columns, CPU rate and share of the mix, and the range
  fractions.  A query picks a range fraction, a start and a kind from the
  stream's ``random.Random``, in that order, as
  ``repro.core.workload.micro_query`` does.  The accessed bytes are the
  kinds' columns, whole.
* ``query_templates`` (the paper's TPC-H throughput run, §4.2): templates,
  each a CPU rate and one scan per table it touches, with the scan's
  columns and the fraction of the table it reads.  Each stream runs
  whole permutations of the templates, rotated by the stream's index and
  then shuffled, as ``repro.core.workload.tpch_streams`` does; each scan
  draws its start.  The accessed bytes are the unique bytes of the pages
  the streams touch, as ``tpch_accessed_bytes`` reckons them.

The same configuration and seed give the program's own streams.  Each
generator takes ``lib``, the module pair that provides ``Database`` and
``ScanSpec``: :data:`PROGRAM` builds the program's own objects (the timed
path), :data:`REFERENCE` the regression copy's (``bench.reference``).
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


def accessed_bytes(db, config: dict, streams=None) -> int:
    """The working set: every column some query kind reads, whole, whatever
    the streams drawn."""
    total = 0
    for name in config["tables"]:
        cols = sorted({c for k in config["queries"] if k["table"] == name
                       for c in k["columns"]})
        if cols:
            total += db.tables[name].total_bytes(cols)
    return total


def template_scans(lib, db, template: dict, rng: random.Random,
                   stream: int) -> list:
    """One instance of a template: a scan of each table it touches, in
    order, each over a random range of its fraction of the table."""
    out = []
    for scan in template["scans"]:
        n_tuples = db.tables[scan["table"]].n_tuples
        length = max(1, int(n_tuples * scan["fraction"]))
        start = rng.randrange(0, max(1, n_tuples - length + 1))
        out.append(lib.ScanSpec(
            table=scan["table"],
            columns=tuple(scan["columns"]),
            ranges=((start, start + length),),
            tuple_rate=template["rate"],
            stream=stream,
        ))
    return out


def template_streams(lib, db, config: dict, n_streams: int,
                     queries_per_stream: int, seed: int) -> List[list]:
    """Each stream drawn whole (every template once, in as many
    permutations as ``queries_per_stream`` template instances need), then
    cut to its first ``queries_per_stream`` instances: a shorter mix is a
    prefix of a longer one."""
    templates = config["templates"]
    n = len(templates)
    rng = random.Random(seed)
    out = []
    for s in range(n_streams):
        queries = []
        for _ in range(max(1, -(-queries_per_stream // n))):
            order = list(range(n))
            order = order[s % n:] + order[:s % n]
            rng.shuffle(order)
            queries += [template_scans(lib, db, templates[i], rng, s)
                        for i in order]
        out.append([scan for q in queries[:queries_per_stream] for scan in q])
    return out


def touched_bytes(db, config: dict, streams: List[list]) -> int:
    """The working set: the unique bytes of the pages the streams touch."""
    seen = set()
    total = 0
    for stream in streams:
        for spec in stream:
            table = db.tables[spec.table]
            for c in spec.columns:
                for a, b in spec.ranges:
                    for p in table.columns[c].pages_for_range(a, b):
                        if p.pid not in seen:
                            seen.add(p.pid)
                            total += p.size_bytes
    return total


#: each generator by its name in a configuration: the streams of a
#: traffic mix, ``(lib, db, config, n_streams, queries_per_stream, seed)``,
#: and the accessed bytes the pools are a share of, ``(db, config, streams)``
GENERATORS = {
    "range_scans": (streams, accessed_bytes),
    "query_templates": (template_streams, touched_bytes),
}
