"""The benchmark's generators against the program's: the same scans from
the same seed, and the configuration's widths where they depart."""

import hashlib

import pytest

from bench import cells, workgen, workload
from repro.core import workload as program


@pytest.fixture(scope="module")
def config():
    return cells.load_config("micro_lineitem_sf30")


@pytest.mark.parametrize("seed", [3, 7])
def test_micro_streams_match_the_program(config, seed):
    """ScanSpec for ScanSpec: ranges, columns, rates and slots."""
    lib = workgen.lib_for(workgen.PROGRAM)
    ours = workgen.streams(lib, workgen.make_db(lib, config), config, 8, 16,
                           seed)
    theirs = program.micro_streams(program.make_lineitem_db(), n_streams=8,
                                   queries_per_stream=16, seed=seed)
    assert ours == theirs


def test_widths_give_the_papers_volume(config):
    """The configuration scales the program's widths by one factor, so
    that the Q1 and Q6 columns take the paper's ~1.55 GB."""
    lib = workgen.lib_for(workgen.PROGRAM)
    db = workgen.make_db(lib, config)
    assert workgen.accessed_bytes(db, config) == pytest.approx(1.55e9,
                                                               rel=1e-3)
    widths = config["tables"]["lineitem"]["columns"]
    ratios = {c: widths[c] / w for c, w in program.LINEITEM_COLUMNS.items()}
    assert set(ratios) == set(widths)
    assert max(ratios.values()) == pytest.approx(min(ratios.values()),
                                                 rel=1e-3)
    page_counts = [len(c.pages) for c in db.tables["lineitem"].columns.values()]
    assert sum(page_counts) == 3635


def test_the_reference_side_builds_equal_streams(config):
    """The regression copy's own classes carry the same scans and pages."""
    prog, ref = (workgen.lib_for(s) for s in (workgen.PROGRAM,
                                              workgen.REFERENCE))
    db_p, db_r = workgen.make_db(prog, config), workgen.make_db(ref, config)
    sp = workgen.streams(prog, db_p, config, 8, 16, 11)
    sr = workgen.streams(ref, db_r, config, 8, 16, 11)
    assert [[vars(x) for x in s] for s in sp] == [[vars(x) for x in s] for s in sr]
    assert workgen.accessed_bytes(db_p, config) \
        == workgen.accessed_bytes(db_r, config)


def test_a_large_seed_is_taken(config):
    lib = workgen.lib_for(workgen.PROGRAM)
    db = workgen.make_db(lib, config)
    a = workgen.streams(lib, db, config, 8, 16, 2**31 + 12345)
    assert a == workgen.streams(lib, db, config, 8, 16, 2**31 + 12345)
    assert a != workgen.streams(lib, db, config, 8, 16, 2**31 + 12346)


#: the micro cells' inputs, ``(streams, accessed bytes, lanes)`` as
#: ``workload.build`` gives them, before the template generator came
MICRO_DIGESTS = {
    ("buf40", 3): "85af213fba4cc55c201669619d26da28973d7d431524a57ffd4d2159ab479987",
    ("buf40", 7): "29d5676deccd1d11938a6c9d42518160b576e5998757408269ce40a4d04fc120",
    ("buf10", 3): "819bd93dd0a08ec268e84ac453d74047d7431ef22e7d645d467777b676edd268",
    ("buf10", 7): "c3c2c3f3c5083137f564ab5ea01906348ffdd0cd843071aff67311b8034bd3f2",
}


@pytest.mark.parametrize("traffic,seed", sorted(MICRO_DIGESTS))
def test_micro_inputs_are_pinned(config, traffic, seed):
    """The micro cells' scans, their 1.55 GB and their pools, bit for bit;
    P = 3,635 pages, 3,712 padded."""
    from repro.core.array_sim import compile_workload

    wl = workload.build(config, cells.load_traffic(traffic), seed)
    got = repr((wl.streams, wl.accessed_bytes, wl.lanes)).encode()
    assert hashlib.sha256(got).hexdigest() == MICRO_DIGESTS[traffic, seed]
    assert wl.accessed_bytes == 1549998000
    assert compile_workload(wl.db, wl.streams).n_pages == 3712


@pytest.fixture(scope="module")
def tpch():
    """The program's TPC-H (``_TPCH_TABLES``, ``_TPCH_QUERIES``) written
    out as a ``query_templates`` configuration at scale 0.05."""
    tables = {name: {"tuples": tuples, "chunks": 90,
                     "min_chunk_tuples": 10_000, "columns": dict(cols)}
              for name, tuples, cols in program._TPCH_TABLES}
    templates = []
    for i, q in enumerate(program._TPCH_QUERIES):
        parts = [(q.table, q.n_cols, q.fraction), *q.extra_tables]
        templates.append({"name": f"Q{i + 1}", "rate": q.rate, "scans": [
            {"table": t, "columns": sorted(tables[t]["columns"])[:n],
             "fraction": frac} for t, n, frac in parts]})
    return {"name": "tpch", "generator": "query_templates", "scale": 0.05,
            "page_bytes": 512 << 10, "tables": tables,
            "templates": templates}


def _templates(side, cfg, n_streams, queries, seed):
    lib = workgen.lib_for(side)
    db = workgen.make_db(lib, cfg)
    return db, workgen.template_streams(lib, db, cfg, n_streams, queries,
                                        seed)


def test_tpch_streams_match_the_program(tpch):
    """ScanSpec for ScanSpec, and the unique bytes they touch."""
    db, ours = _templates(workgen.PROGRAM, tpch, 8, 22, 7)
    db_p = program.make_tpch_db(0.05)
    theirs = program.tpch_streams(db_p, n_streams=8, seed=7)
    assert ours == theirs
    assert len({s.table for st in ours for s in st}) == 6
    assert workgen.touched_bytes(db, tpch, ours) \
        == program.tpch_accessed_bytes(db_p, theirs)


def test_the_reference_side_builds_equal_template_streams(tpch):
    db_p, sp = _templates(workgen.PROGRAM, tpch, 8, 22, 7)
    db_r, sr = _templates(workgen.REFERENCE, tpch, 8, 22, 7)
    assert [[vars(x) for x in s] for s in sp] \
        == [[vars(x) for x in s] for s in sr]
    assert workgen.touched_bytes(db_p, tpch, sp) \
        == workgen.touched_bytes(db_r, tpch, sr)


def _instances(cfg, stream):
    """A stream's scans, cut back into template instances: each template
    is known by its rate, which the caller makes its own."""
    by_rate = {t["rate"]: t for t in cfg["templates"]}
    assert len(by_rate) == len(cfg["templates"])
    out, i = [], 0
    while i < len(stream):
        scans = by_rate[stream[i].tuple_rate]["scans"]
        assert [s.table for s in stream[i:i + len(scans)]] \
            == [s["table"] for s in scans]
        out.append(stream[i:i + len(scans)])
        i += len(scans)
    return out


@pytest.mark.parametrize("short,long", [(4, 22), (30, 44)])
def test_a_shorter_template_mix_is_a_prefix(tpch, short, long):
    """Streams are drawn whole permutations at a time and then cut, so a
    mix of fewer instances begins each stream of a longer one."""
    cfg = dict(tpch, templates=[dict(t, rate=t["rate"] + i)
                                for i, t in enumerate(tpch["templates"])])
    _, a = _templates(workgen.PROGRAM, cfg, 8, short, 7)
    _, b = _templates(workgen.PROGRAM, cfg, 8, long, 7)
    for sa, sb in zip(a, b):
        assert sa == sb[:len(sa)]
        assert len(_instances(cfg, sa)) == short
        assert len(_instances(cfg, sb)) == long
