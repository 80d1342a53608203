"""A cell of ``BENCHMARK.json`` resolved to the files that define it.

Everything here is looked up by name, so a later PR adds a cell, a
configuration, a traffic mix, a statement or a per-layer metric by adding
files and one entry to ``BENCHMARK.json`` and edits nothing that is there:

    configs/<config>.json         the deployment as it is run
    traffic/<mix>.json            parameters of the one traffic generator
    statements/<name>.sql         a statement's text, ``{param}`` slots
    reference/<name>.py           its plain reference: TABLES, COLUMNS,
                                  bind(params), answer(tables, params)
    layer_metrics/<metric>.py     one reader: read(reading) -> value | None
                                  (a metric named ``<metric>.<suffix>``,
                                  split because its cells report different
                                  end-to-end metrics, shares <metric>.py)
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: str = BENCH):
    """The module ``<bench>/<kind>/<name>.py``, found by its file name
    (a metric's name may hold ``.`` or ``-``, which no import allows)."""
    path = os.path.join(bench, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench: str = BENCH):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``,
    or, for a split metric ``<base>.<suffix>`` without a file of its
    own, ``layer_metrics/<base>.py``."""
    base = metric.split(".", 1)[0]
    own = os.path.isfile(os.path.join(bench, "layer_metrics", metric + ".py"))
    return load_module("layer_metrics", metric if own else base, bench).read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix,
    the statements the mix sends and the metrics the cell reports."""

    def __init__(self, name: str, repo: str = REPO):
        self.repo = repo
        self.bench = os.path.join(repo, os.path.basename(BENCH))
        bm = read_json(repo, "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"it has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        self.run_seconds = int(bm["run_seconds"])
        cfg_entry = {c["name"]: c for c in bm["configs"]}[self.entry["config"]]
        self.config = read_json(repo, cfg_entry["file"])
        self.traffic = read_json(self.bench, "traffic",
                                 self.entry["traffic"] + ".json")
        self.end_to_end = [m for m in bm["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in bm["per_layer"] if _applies(m, name)]
        self.statements = {}
        for stmt in self.traffic["statements"]:
            if stmt not in self.config["statements"]:
                raise KeyError(f"mix {self.entry['traffic']!r} sends "
                               f"{stmt!r}, which configuration "
                               f"{self.entry['config']!r} does not serve")
            with open(os.path.join(self.bench, "statements", stmt + ".sql"),
                      encoding="utf-8") as f:
                text = f.read()
            self.statements[stmt] = (
                text, load_module("reference", stmt, self.bench))

    def metrics(self, trace: bool) -> list:
        """The metric entries a run of this cell has to print."""
        return self.per_layer if trace else self.end_to_end

    def tables(self) -> list:
        """The configuration's tables that the mix's statements read, in
        the configuration's order: what a run has to load."""
        used = {t for _, ref in self.statements.values() for t in ref.TABLES}
        return [t for t in self.config["tables"] if t in used]

    def reference_columns(self) -> dict:
        """table -> the columns the references read (what load keeps)."""
        out: dict = {}
        for _, ref in self.statements.values():
            for table, cols in ref.COLUMNS.items():
                out.setdefault(table, set()).update(cols)
        return out

    def scanned_bytes(self, stmt: str, rows: dict) -> int:
        """Bytes one statement has to read at the least: for each column
        its text names, the narrowest whole-byte width that holds the
        column's TPC-H domain (the configuration's ``column_bytes``, NOT
        the engine's dtypes), times the table's rows."""
        _, ref = self.statements[stmt]
        widths = self.config["column_bytes"]
        return sum(int(widths[c]["bytes"]) * int(rows[table])
                   for table, cols in ref.COLUMNS.items() for c in cols)
