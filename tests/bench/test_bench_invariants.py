"""The configuration's guarantees on hand-made lane states: a sound lane
keeps each, and each breach is read."""

import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import cells, invariants, workgen
from benchtiny import DATA

PAGE = 524288
CONFIG = {"page_bytes": PAGE,
          "tables": {"t": {"columns": {"a": 4 * PAGE / 1000.0}}}}


def plan():
    # one stream: a query over tuples [0, 500) at 1000 tuples/s, then one
    # over [500, 1000); the column has 4 pages of 250 tuples
    q = [NS(table="t", columns=("a",), ranges=((0, 500),), tuple_rate=1e3),
         NS(table="t", columns=("a",), ranges=((500, 1000),), tuple_rate=1e3)]
    return invariants.Plan(CONFIG, [q], {"t": 1000})


def lane(**kw):
    sound = {"t": np.array([1.0]), "qidx": np.array([[1]]),
             "pos": np.array([[100.0]]), "io_bytes": np.array([3.0 * PAGE]),
             "loads": np.array([3]), "resident": np.array([[1, 1, 1, 0]])}
    sound.update({k: np.asarray(v) for k, v in kw.items()})
    return sound


def read(job):
    return invariants.numbers(job, plan(), [2 * PAGE], 3 * PAGE, 1.0)


def test_page_ranges_match_the_deployments_pages():
    cfg = cells.load_config("micro_lineitem_sf30")
    cfg["scale"] = 0.01
    db = workgen.make_db(workgen.lib_for(workgen.PROGRAM), cfg)
    t = db.tables["lineitem"]
    for name, width in cfg["tables"]["lineitem"]["columns"].items():
        b = invariants.page_ranges(t.n_tuples, width, PAGE)
        pages = t.columns[name].pages
        assert list(b[:-1]) == [p.first_tuple for p in pages]
        assert b[-1] == pages[-1].last_tuple


def test_page_ranges_match_every_table_of_a_multi_table_deployment():
    """The same on each table of the tests' three-table configuration,
    where a column of ``orders`` and of ``customer`` is one page."""
    with open(os.path.join(DATA, "three_tables.json")) as f:
        cfg = json.load(f)
    db = workgen.make_db(workgen.lib_for(workgen.PROGRAM), cfg)
    for tname, table in cfg["tables"].items():
        t = db.tables[tname]
        for name, width in table["columns"].items():
            b = invariants.page_ranges(t.n_tuples, width, PAGE)
            pages = t.columns[name].pages
            assert list(b[:-1]) == [p.first_tuple for p in pages]
            assert b[-1] == pages[-1].last_tuple


def test_a_sound_lane_keeps_every_guarantee():
    got = read(lane())
    assert got == pytest.approx({"short_lanes": 0.0, "cpu_excess": -0.4,
                                 "io_excess": 3 / 4 - 1,
                                 "unloaded_pages": -1.0,
                                 "pool_excess_pages": -0.0})
    assert all(got[k] <= v for k, v in invariants.LIMITS.items())


@pytest.mark.parametrize("kw,name", [
    ({"t": [0.5]}, "short_lanes"),
    ({"pos": [[600.0]]}, "cpu_excess"),
    ({"io_bytes": [4.5 * PAGE]}, "io_excess"),
    ({"loads": [1]}, "unloaded_pages"),
    ({"resident": [[1, 1, 1, 1]]}, "pool_excess_pages"),
], ids=["clock", "cpu", "io", "loads", "pool"])
def test_each_breach_is_read(kw, name):
    got = read(lane(**kw))
    assert got[name] > invariants.LIMITS[name]
