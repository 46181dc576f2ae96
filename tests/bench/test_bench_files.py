"""Every file the benchmark names is found by its name and is valid, and a
cell added from files alone is picked up with no other file edited."""

import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import cells, metrics, workgen, workload
from benchtiny import DATA

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:3] == ["python3", "-m", "bench.run"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(cells.ROOT, p)), p
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) < 64 << 10


def test_names_units_and_bounds(bench):
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in names
            names.add((group, entry["name"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("kind", ["configs", "workloads", "per_layer"])
def test_every_named_file_is_found_and_valid(bench, kind):
    for entry in bench[kind]:
        if kind == "configs":
            cfg = cells.load_config(entry["name"])
            assert os.path.relpath(
                os.path.join(cells.BENCH_DIR, "configs", entry["name"] + ".json"),
                cells.ROOT) == entry["file"]
            assert set(entry["reduced"]) <= set(cfg)
            assert cfg["reduced"] == entry["reduced"]
        elif kind == "workloads":
            cell = cells.find_cell(entry["name"], bench)
            assert cell.chips in (1, 4)
            assert cell.config["name"] == entry["config"]
            reported = {m["name"] for m in cell.end_to_end}
            assert "setup_s" in reported and len(reported) >= 2
            assert cell.per_layer
        else:
            assert callable(metrics.reader(entry["name"]))


def test_every_config_is_used(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_a_cell_added_from_files_alone_is_picked_up(bench, tmp_path):
    """A later change adds a configuration, a traffic mix and a metric as
    new files plus entries; no file already there is edited."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub), bench_dir / sub)
    cfg = json.loads((bench_dir / "configs" / "micro_lineitem_sf30.json")
                     .read_text())
    cfg["name"] = "micro_lineitem_sf10"
    cfg["scale"] = 1 / 3
    (bench_dir / "configs" / "micro_lineitem_sf10.json").write_text(
        json.dumps(cfg))
    tr = json.loads((bench_dir / "traffic" / "buf10.json").read_text())
    tr.update(name="bw1200", bandwidth_bps=1.2e9)
    (bench_dir / "traffic" / "bw1200.json").write_text(json.dumps(tr))
    (bench_dir / "layer_metrics" / "lanes_done.py").write_text(
        "def read(ctx):\n    return float((ctx.jobs[-1]['t'] > 0).sum())\n")
    extended = json.loads(json.dumps(bench))
    extended["configs"].append({"name": "micro_lineitem_sf10"})
    extended["workloads"].append({"name": "micro_sf10.bw1200",
                                  "config": "micro_lineitem_sf10",
                                  "traffic": "bw1200",
                                  "chips": 1, "why": "throwaway"})
    extended["per_layer"].append({"name": "lanes_done", "unit": "lanes",
                                  "better": "higher",
                                  "source": "program_counter",
                                  "layer": "time engine",
                                  "moves": "sim_s_per_s",
                                  "workloads": ["micro_sf10.bw1200"]})
    cell = cells.find_cell("micro_sf10.bw1200", extended, str(bench_dir))
    assert cell.config["scale"] == pytest.approx(1 / 3)
    assert cell.traffic["bandwidth_bps"] == 1.2e9
    wl = workload.build(cell.config, cell.traffic, 5)
    assert wl.accessed_bytes == pytest.approx(1.55e9 / 3, rel=1e-3)
    assert "lanes_done" in {m["name"] for m in cell.per_layer}
    ctx = metrics.Context(jobs=[{"t": np.array([1.5, 0.0]),
                                 "steps": np.array([10, 0])}],
                          traced_job=None, lanes_per_shard=2, shards=1,
                          pages=8, trace=None, peak=None)
    got = metrics.per_layer([m for m in cell.per_layer
                             if m["name"] == "lanes_done"], ctx,
                            str(bench_dir))
    assert got == {"lanes_done": {"value": 1.0, "unit": "lanes"}}
    # the cells already there are unchanged by the addition
    assert cells.find_cell("micro_sf30.buf10", extended, str(bench_dir)).traffic \
        == cells.find_cell("micro_sf30.buf10", bench).traffic


def test_a_multi_table_cell_added_from_files_alone_is_picked_up(bench,
                                                               tmp_path):
    """A later change adds a ``query_templates`` configuration of three
    tables and its traffic mix as new files plus entries; no file already
    there is edited, and the cells already there build as before."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub), bench_dir / sub)
    cfg = json.loads(open(os.path.join(DATA, "three_tables.json")).read())
    cfg.update(name="three_tables_sf30", scale=1.0)
    (bench_dir / "configs" / "three_tables_sf30.json").write_text(
        json.dumps(cfg))
    tr = json.loads((bench_dir / "traffic" / "buf10.json").read_text())
    tr.update(name="buf30", bandwidth_bps=6e8, pool_fractions=[0.3],
              queries_per_stream=9)
    (bench_dir / "traffic" / "buf30.json").write_text(json.dumps(tr))
    extended = json.loads(json.dumps(bench))
    extended["configs"].append({"name": "three_tables_sf30"})
    extended["workloads"].append({"name": "three_tables_sf30.buf30",
                                  "config": "three_tables_sf30",
                                  "traffic": "buf30",
                                  "chips": 1, "why": "throwaway"})
    cell = cells.find_cell("three_tables_sf30.buf30", extended,
                           str(bench_dir))
    assert cell.config["generator"] == "query_templates"
    assert {m["name"] for m in cell.end_to_end} == {"sim_s_per_s", "setup_s"}
    wl = workload.build(cell.config, cell.traffic, 5)
    assert len(wl.streams) == 8
    assert {s.table for st in wl.streams for s in st} == set(cfg["tables"])
    # 9 template instances a stream: two whole permutations and one more
    scans = sum(len(t["scans"]) for t in cfg["templates"])
    assert all(2 * scans + 1 <= len(st) <= 2 * scans + 3 for st in wl.streams)
    assert wl.accessed_bytes == workgen.touched_bytes(wl.db, cell.config,
                                                      wl.streams)
    assert 0 < wl.accessed_bytes < sum(
        wl.db.tables[t].total_bytes(list(cfg["tables"][t]["columns"]))
        for t in cfg["tables"])
    assert [cap for _, _, cap in wl.lanes] \
        == [int(0.3 * wl.accessed_bytes)] * 4
    # the cells already there are unchanged by the addition
    for name in ("micro_sf30.buf40", "micro_sf30.buf10"):
        old = cells.find_cell(name, bench)
        new = cells.find_cell(name, extended, str(bench_dir))
        assert (new.config, new.traffic) == (old.config, old.traffic)
        assert workload.build(new.config, new.traffic, 5).streams \
            == workload.build(old.config, old.traffic, 5).streams


def _no_scans(cfg):
    cfg["templates"][0]["scans"] = []


def _unknown_table(cfg):
    cfg["templates"][0]["scans"][0]["table"] = "nation"


def _unknown_column(cfg):
    cfg["templates"][0]["scans"][0]["columns"].append("l_comment")


def _fraction(value):
    def edit(cfg):
        cfg["templates"][0]["scans"][0]["fraction"] = value
    return edit


def _rate_zero(cfg):
    cfg["templates"][0]["rate"] = 0.0


def _no_templates(cfg):
    cfg["templates"] = []


@pytest.mark.parametrize("edit,message", [
    (_no_scans, "template 'Q1' has no scans"),
    (_unknown_table, "template 'Q1': no table 'nation'"),
    (_unknown_column, r"template 'Q1': table 'lineitem' has no columns "
                      r"\['l_comment'\]"),
    (_fraction(0.0), r"template 'Q1': fraction 0.0 is outside \(0, 1\]"),
    (_fraction(1.5), r"template 'Q1': fraction 1.5 is outside \(0, 1\]"),
    (_rate_zero, "template 'Q1': rate 0.0 is not positive"),
    (_no_templates, "no query templates"),
], ids=["no_scans", "unknown_table", "unknown_column", "fraction_zero",
        "fraction_above_one", "rate_zero", "no_templates"])
def test_a_bad_query_template_is_refused(edit, message, tmp_path):
    cfg = json.loads(open(os.path.join(DATA, "three_tables.json")).read())
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "three_tables.json"
    path.write_text(json.dumps(cfg))
    assert cells.load_config("three_tables", str(tmp_path)) == cfg
    edit(cfg)
    path.write_text(json.dumps(cfg))
    with pytest.raises(cells.CellError, match=message):
        cells.load_config("three_tables", str(tmp_path))


def test_a_bad_file_is_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "odd.json").write_text(json.dumps({
        "name": "odd", "n_streams": 8, "queries_per_stream": 16,
        "stream_seed": 7, "bandwidth_bps": 6e8,
        "policies": ["lru", "pbm", "opt"], "pool_fractions": [0.1],
        "horizon_s": 1.5, "lane_shards": 4}))
    with pytest.raises(cells.CellError, match="do not divide"):
        cells.load_traffic("odd", str(tmp_path))
    with pytest.raises(cells.CellError, match="no such file"):
        cells.load_config("absent", str(tmp_path))
    cfg = cells.load_config("micro_lineitem_sf30")
    cfg["queries"][1]["table"] = "orders"
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "micro_lineitem_sf30.json").write_text(
        json.dumps(cfg))
    with pytest.raises(cells.CellError, match="one of its tables"):
        cells.load_config("micro_lineitem_sf30", str(tmp_path))
