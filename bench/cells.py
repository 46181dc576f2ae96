"""Find a cell of ``BENCHMARK.json`` and the files it names.

A cell names a configuration and a traffic mix; each is a JSON file under
``bench/configs`` or ``bench/traffic`` whose stem is that name.  The
metrics a cell reports are the ``BENCHMARK.json`` metrics that list the
cell under ``workloads``, or that have no ``workloads`` key.

A configuration's ``generator`` says how its streams are drawn
(:mod:`bench.workgen`): ``range_scans``, query kinds over ranges of one
table, or ``query_templates``, templates of one scan per table they
touch.  A traffic mix's ``queries_per_stream`` counts the queries, or
the template instances, of each stream.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List

from . import workgen

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: ``range_scans`` and ``query_templates``
GENERATORS = tuple(workgen.GENERATORS)
#: the keys a configuration needs, by generator: ``range_scans`` ...
CONFIG_KEYS = ("name", "source", "generator", "scale", "page_bytes",
               "tables", "queries", "range_fractions", "time_slice_s",
               "reduced", "assumed")
#: ... and ``query_templates``, whose templates are each
#: ``{"name", "rate", "scans": [{"table", "columns", "fraction"}, ...]}``
TEMPLATE_CONFIG_KEYS = ("name", "source", "generator", "scale", "page_bytes",
                        "tables", "templates", "time_slice_s", "reduced",
                        "assumed")
TEMPLATE_KEYS = ("name", "rate", "scans")
SCAN_KEYS = ("table", "columns", "fraction")
TRAFFIC_KEYS = ("name", "n_streams", "queries_per_stream", "stream_seed",
                "bandwidth_bps", "policies", "pool_fractions", "horizon_s",
                "lane_shards")


class CellError(ValueError):
    """A cell, configuration or traffic file that cannot be run."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"no such file: {os.path.relpath(path, ROOT)}") from None


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    cfg = _load_json(os.path.join(bench_dir, "configs", f"{name}.json"))
    templates = cfg.get("generator") == "query_templates"
    keys = TEMPLATE_CONFIG_KEYS if templates else CONFIG_KEYS
    missing = [k for k in keys if k not in cfg]
    if missing:
        raise CellError(f"config {name!r} lacks {missing}")
    if cfg["name"] != name:
        raise CellError(f"config file {name!r} names itself {cfg['name']!r}")
    if cfg["generator"] not in GENERATORS:
        raise CellError(f"config {name!r}: generator {cfg['generator']!r} "
                        f"is none of {GENERATORS}")
    if templates:
        _check_templates(name, cfg)
        return cfg
    tables = {q["table"] for q in cfg["queries"]}
    if len(tables) != 1 or not tables <= set(cfg["tables"]):
        raise CellError(f"config {name!r}: the query kinds must range over "
                        f"one of its tables, not {sorted(tables)}")
    if abs(sum(q["share"] for q in cfg["queries"]) - 1.0) > 1e-9:
        raise CellError(f"config {name!r}: the query shares do not sum to 1")
    return cfg


def _check_templates(name: str, cfg: dict) -> None:
    """Each query template runs at least one scan, at a positive rate,
    over columns of a table of the configuration and a share of its
    tuples in (0, 1]."""
    if not cfg["templates"]:
        raise CellError(f"config {name!r}: no query templates")
    for i, t in enumerate(cfg["templates"]):
        where = f"config {name!r}: template {t.get('name', i)!r}"
        missing = [k for k in TEMPLATE_KEYS if k not in t]
        if missing:
            raise CellError(f"{where} lacks {missing}")
        if not t["rate"] > 0:
            raise CellError(f"{where}: rate {t['rate']!r} is not positive")
        if not t["scans"]:
            raise CellError(f"{where} has no scans")
        for scan in t["scans"]:
            missing = [k for k in SCAN_KEYS if k not in scan]
            if missing:
                raise CellError(f"{where}: a scan lacks {missing}")
            table = cfg["tables"].get(scan["table"])
            if table is None:
                raise CellError(f"{where}: no table {scan['table']!r}")
            absent = [c for c in scan["columns"] if c not in table["columns"]]
            if absent:
                raise CellError(f"{where}: table {scan['table']!r} has no "
                                f"columns {absent}")
            if not 0 < scan["fraction"] <= 1:
                raise CellError(f"{where}: fraction {scan['fraction']!r} is "
                                "outside (0, 1]")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    tr = _load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))
    missing = [k for k in TRAFFIC_KEYS if k not in tr]
    if missing:
        raise CellError(f"traffic {name!r} lacks {missing}")
    if tr["name"] != name:
        raise CellError(f"traffic file {name!r} names itself {tr['name']!r}")
    n_lanes = len(tr["policies"]) * len(tr["pool_fractions"])
    if tr["lane_shards"] < 1 or n_lanes % tr["lane_shards"]:
        raise CellError(f"traffic {name!r}: {n_lanes} lanes do not divide "
                        f"into {tr['lane_shards']} lane shards")
    if not tr["horizon_s"] > 0:
        raise CellError(f"traffic {name!r}: horizon_s must be positive")
    return tr


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: dict = None,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = load_benchmark() if bench is None else bench
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise CellError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    traffic = load_traffic(w["traffic"], bench_dir)
    if traffic["lane_shards"] not in (1, w["chips"]):
        raise CellError(f"workload {name!r} asks for {w['chips']} chips, "
                        f"its traffic for {traffic['lane_shards']} shards")
    return Cell(
        name=name,
        chips=w["chips"],
        config=load_config(w["config"], bench_dir),
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )
