"""The ``tpch_sf30`` configuration and its cell ``tpch_sf30.buf10``: the
specification's tables, columns and templates, the widths it shares with
the micro configuration, its inputs pinned by digest, and the kernels'
work at its page count."""

import datetime
import hashlib
import math

import pytest

from bench import cells, kernel_work, peaks, workload

CELL = "tpch_sf30.buf10"

#: the TPC-H tables at SF30 (clauses 1.2, 4.2.5) and the columns the 22
#: queries of clause 2.4 read (53 of the 61)
TABLES = {
    "lineitem": (180_000_000, [
        "l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
        "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
        "l_shipinstruct", "l_shipmode"]),
    "orders": (45_000_000, [
        "o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
        "o_orderdate", "o_orderpriority", "o_shippriority", "o_comment"]),
    "partsupp": (24_000_000, [
        "ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost"]),
    "part": (6_000_000, [
        "p_partkey", "p_name", "p_mfgr", "p_brand", "p_type", "p_size",
        "p_container"]),
    "customer": (4_500_000, [
        "c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
        "c_acctbal", "c_mktsegment", "c_comment"]),
    "supplier": (300_000, [
        "s_suppkey", "s_name", "s_address", "s_nationkey", "s_phone",
        "s_acctbal", "s_comment"]),
    "nation": (25, ["n_nationkey", "n_name", "n_regionkey"]),
    "region": (5, ["r_regionkey", "r_name"]),
}

_D = datetime.date
#: the scans restricted to a range of dates, at the validation parameters
#: of clause 2.4.x.3: (template, table) -> the first and last day admitted
DATE_RANGES = {
    ("Q1", "lineitem"): (_D(1992, 1, 1), _D(1998, 9, 2)),
    ("Q3", "orders"): (_D(1992, 1, 1), _D(1995, 3, 14)),
    ("Q3", "lineitem"): (_D(1995, 3, 16), _D(1998, 8, 2)),
    ("Q4", "orders"): (_D(1993, 7, 1), _D(1993, 9, 30)),
    ("Q5", "orders"): (_D(1994, 1, 1), _D(1994, 12, 31)),
    ("Q6", "lineitem"): (_D(1994, 1, 1), _D(1994, 12, 31)),
    ("Q7", "lineitem"): (_D(1995, 1, 1), _D(1996, 12, 31)),
    ("Q8", "orders"): (_D(1995, 1, 1), _D(1996, 12, 31)),
    ("Q10", "orders"): (_D(1993, 10, 1), _D(1993, 12, 31)),
    ("Q12", "lineitem"): (_D(1994, 1, 1), _D(1994, 12, 31)),
    ("Q14", "lineitem"): (_D(1995, 9, 1), _D(1995, 9, 30)),
    ("Q15", "lineitem"): (_D(1996, 1, 1), _D(1996, 3, 31)),
    ("Q20", "lineitem"): (_D(1994, 1, 1), _D(1994, 12, 31)),
}
#: the order-date domain, 1992-01-01 to 1998-08-02 (clause 4.2.3)
FIRST_DAY, LAST_DAY = _D(1992, 1, 1), _D(1998, 8, 2)


@pytest.fixture(scope="module")
def config():
    return cells.load_config("tpch_sf30")


def test_the_eight_tables_hold_the_columns_the_queries_read(config):
    tables = config["tables"]
    assert {t: (v["tuples"], list(v["columns"])) for t, v in tables.items()} \
        == TABLES
    assert sum(len(v["columns"]) for v in tables.values()) == 53
    read = {(s["table"], c) for t in config["templates"]
            for s in t["scans"] for c in s["columns"]}
    assert read == {(t, c) for t, (_, cols) in TABLES.items() for c in cols}


def test_every_templates_tables_and_columns_exist(config):
    """22 templates, Q1 to Q22, each one scan per table it reads, over
    columns of that table, at the program's rate for its query."""
    from repro.core import workload as program

    templates = config["templates"]
    assert [t["name"] for t in templates] == [f"Q{i}" for i in range(1, 23)]
    for t, q in zip(templates, program._TPCH_QUERIES):
        assert t["rate"] == q.rate
        tables = [s["table"] for s in t["scans"]]
        assert len(set(tables)) == len(tables), t["name"]
        for s in t["scans"]:
            assert s["columns"], (t["name"], s["table"])
            assert set(s["columns"]) <= set(TABLES[s["table"]][1])
    assert sum(len(t["scans"]) for t in templates) == 72


def test_date_predicates_read_their_share_of_the_order_date_domain(config):
    domain = (LAST_DAY - FIRST_DAY).days + 1
    assert domain == 2406
    for t in config["templates"]:
        for s in t["scans"]:
            days = DATE_RANGES.get((t["name"], s["table"]))
            if days is None:
                assert s["fraction"] == 1.0, (t["name"], s["table"])
                continue
            first, last = max(days[0], FIRST_DAY), min(days[1], LAST_DAY)
            share = ((last - first).days + 1) / domain
            assert s["fraction"] == pytest.approx(share, abs=1e-6), \
                (t["name"], s["table"])
    q6 = next(t for t in config["templates"] if t["name"] == "Q6")
    assert q6["scans"][0]["fraction"] == round(365 / 2406, 6)


def test_lineitem_widths_shared_with_the_micro_configuration(config):
    """The same table at the same scale: the columns the micro
    configuration has take its widths exactly."""
    micro = cells.load_config("micro_lineitem_sf30")["tables"]["lineitem"]
    ours = config["tables"]["lineitem"]
    assert micro["tuples"] == ours["tuples"]
    assert set(micro["columns"]) < set(ours["columns"])
    for c, w in micro["columns"].items():
        assert ours["columns"][c] == w, c


#: the cell's inputs, ``(streams, touched bytes, lanes)`` as
#: ``workload.build`` gives them
TPCH_DIGESTS = {
    3: "582ee8287e656f91e8afc46a01ed4f7f2f626c8e7f9a6429b70174278783efc4",
    7: "5d1f2674fe7c3803ed386a5684dd0bb6a961a8ceaf01dd5c35c971c8d24193ec",
}
TOUCHED_BYTES = 7_500_051_782
PAGES = 14_336


@pytest.mark.parametrize("seed", sorted(TPCH_DIGESTS))
def test_tpch_inputs_are_pinned(seed):
    """The cell's scans, its ~7.5 GB and its 750 MB pools, bit for bit;
    P = 14,335 pages, 14,336 padded."""
    from repro.core.array_sim import compile_workload

    cell = cells.find_cell(CELL)
    wl = workload.build(cell.config, cell.traffic, seed)
    got = repr((wl.streams, wl.accessed_bytes, wl.lanes)).encode()
    assert hashlib.sha256(got).hexdigest() == TPCH_DIGESTS[seed]
    assert wl.accessed_bytes == TOUCHED_BYTES
    assert abs(wl.accessed_bytes / 7.5e9 - 1) <= 0.05
    assert [cap for _, _, cap in wl.lanes] == [TOUCHED_BYTES // 10] * 4
    spec = compile_workload(wl.db, wl.streams)
    assert spec.n_pages == PAGES
    assert int(spec.page_valid.sum()) == PAGES - 1
    assert (spec.n_tables, spec.n_cols, spec.n_streams, spec.n_queries) \
        == (8, 53, 8, 72)


def test_kernel_work_at_the_cells_pages():
    """Each op's bytes and operations for the cell's four lanes of
    14,336 pages, and its least time on a v5e, bound by the bytes."""
    lanes, pages = 4, PAGES
    assert kernel_work.work("batched_evict", lanes, pages) == {
        "bytes": 4.0 * (10 * pages + 4), "ops": 4.0 * pages * 2}
    assert kernel_work.work("fifo_grant", lanes, pages) == {
        "bytes": 4.0 * (9 * pages + 16), "ops": 4.0 * pages * 2}
    wake = kernel_work.work("wake_solve", lanes, pages)
    assert wake["bytes"] == 4.0 * (12 * pages + 12)
    assert wake["ops"] == pytest.approx(4 * pages * (math.log2(pages) + 1))
    peak = peaks.peaks("TPU v5 lite")
    for op in kernel_work.OPS:
        w = kernel_work.work(op, lanes, pages)
        assert kernel_work.least_seconds(op, lanes, pages, peak) \
            == pytest.approx(w["bytes"] / 819e9)
