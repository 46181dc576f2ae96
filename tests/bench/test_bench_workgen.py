"""The benchmark's generator against the program's: the same scans from
the same seed, and the configuration's widths where they depart."""

import pytest

from bench import cells, workgen
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
