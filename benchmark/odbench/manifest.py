"""``BENCHMARK.json`` and the files it names.

The manifest lists configurations, cells and metrics by name; what belongs
to one of them sits in a file of its own, found by that name:

    <bench>/configs/<configuration>.json   (the manifest gives the path)
    <bench>/traffic/<traffic>.json
    <bench>/workloads/<cell>.json
    <bench>/metrics/<metric>.json   ->   <bench>/readers/<reader>.py
    <bench>/drivers/<traffic kind>.py

Nothing here knows the name of a cell, a configuration or a metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, nested dicts merged key by key."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = value
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config_path: str
    config: dict  # the configuration as it is run
    traffic_name: str
    traffic: dict
    options: dict  # the cell's own file: trainer or engine options, checks
    root: str
    bench_dir: str


class Manifest:
    def __init__(self, root: str, bench_dir: str):
        self.root = root
        self.bench_dir = bench_dir
        self.raw = _read(os.path.join(root, "BENCHMARK.json"))

    # -- cells ---------------------------------------------------------------

    def cell(self, name: str, rehearse: bool = False) -> Cell:
        entry = next((w for w in self.raw["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in self.raw["workloads"])
            raise KeyError(f"no cell {name!r} in BENCHMARK.json (cells: {known})")
        conf = next(c for c in self.raw["configs"] if c["name"] == entry["config"])
        config_path = os.path.join(self.root, conf["file"])
        config = _read(config_path)
        traffic = _read(os.path.join(self.bench_dir, "traffic", entry["traffic"] + ".json"))
        options = _read(os.path.join(self.bench_dir, "workloads", name + ".json"))
        if rehearse:
            # the cell's own tiny preset, for a run without the chip
            tiny = options.get("rehearse", {})
            config = _merge(config, tiny.get("config", {}))
            traffic = _merge(traffic, tiny.get("traffic", {}))
            options = _merge(options, tiny.get("options", {}))
        return Cell(
            name=name,
            chips=int(entry["chips"]),
            config_name=conf["name"],
            config_path=config_path,
            config=config,
            traffic_name=entry["traffic"],
            traffic=traffic,
            options=options,
            root=self.root,
            bench_dir=self.bench_dir,
        )

    # -- metrics -------------------------------------------------------------

    def _applies(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.raw["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list:
        return [m for m in self.raw["per_layer"] if self._applies(m, cell)]

    def metric_file(self, name: str) -> dict:
        return _read(os.path.join(self.bench_dir, "metrics", name + ".json"))

    def reader(self, metric_name: str):
        """-> (read function, params) of a per-layer metric."""
        spec = self.metric_file(metric_name)
        return load_module(
            os.path.join(self.bench_dir, "readers", spec["reader"] + ".py")
        ).read, spec.get("params", {})

    def driver(self, kind: str):
        return load_module(os.path.join(self.bench_dir, "drivers", kind + ".py"))


def load_module(path: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "odbench_file_" + re.sub(r"\W", "_", os.path.relpath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# checks of the manifest against its own contract (the unit tests run these)
# ---------------------------------------------------------------------------


def problems(m: Manifest) -> list:
    """Every way ``m`` breaks the rules the benchmark can check for itself;
    empty when sound."""
    raw, out = m.raw, []
    want = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    # the one optional key: the per-layer metrics come from a traced stretch
    # of the measuring run itself (--trace 2), not from a run of their own
    if set(raw) - {"trace_in_run"} != want:
        out.append(f"keys {sorted(raw)} are not exactly {sorted(want)} (and trace_in_run)")
        return out
    if not isinstance(raw.get("trace_in_run", True), bool):
        out.append("trace_in_run is true or false")
    configs = {c["name"]: c for c in raw["configs"]}
    cells = {w["name"]: w for w in raw["workloads"]}
    e2e = {x["name"]: x for x in raw["end_to_end"]}
    for name in [*configs, *cells, *e2e, *(p["name"] for p in raw["per_layer"])]:
        if not NAME.match(name):
            out.append(f"name {name!r} has characters outside the allowed set")
    for w in cells.values():
        for key in ("config", "traffic"):
            if not NAME.match(w[key]):
                out.append(f"cell {w['name']}: {key} {w[key]!r} is not a name")
        if w["config"] not in configs:
            out.append(f"cell {w['name']}: unknown configuration {w['config']!r}")
        if w["chips"] not in (1, 4):
            out.append(f"cell {w['name']}: chips must be 1 or 4")
        if not 1 <= len(w["why"]) <= 200:
            out.append(f"cell {w['name']}: why must have 1 to 200 characters")
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    if len(set(pairs)) != len(pairs):
        out.append("a pair of configuration and traffic appears twice")
    for c in configs.values():
        if not any(w["config"] == c["name"] for w in cells.values()):
            out.append(f"configuration {c['name']} has no cell")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in raw["paths"]):
            out.append(f"configuration {c['name']}: file outside paths")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} four-chip cells of {len(cells)} is over 25%")
    if "setup_s" not in e2e:
        out.append("no setup_s among the end-to-end metrics")
    for x in e2e.values():
        if x["source"] not in ("host_clock", "device_trace"):
            out.append(f"{x['name']}: an end-to-end metric is host_clock or device_trace")
        if not 0.01 <= x["bound"] <= 0.1:
            out.append(f"{x['name']}: bound {x['bound']} outside [0.01, 0.1]")
    for x in [*e2e.values(), *raw["per_layer"]]:
        if not UNIT.match(x["unit"]):
            out.append(f"{x['name']}: unit {x['unit']!r} not allowed")
        if x["better"] not in ("lower", "higher"):
            out.append(f"{x['name']}: better is lower or higher")
        for cell in x.get("workloads", []):
            if cell not in cells:
                out.append(f"{x['name']}: unknown cell {cell!r}")
    for p in raw["per_layer"]:
        if p["source"] not in SOURCES:
            out.append(f"{p['name']}: unknown source {p['source']!r}")
        if p["moves"] not in e2e:
            out.append(f"{p['name']}: moves unknown metric {p['moves']!r}")
            continue
        for cell in cells:
            if m._applies(p, cell) and not m._applies(e2e[p["moves"]], cell):
                out.append(f"{p['name']}: {p['moves']} is not reported in {cell}")
    for cell in cells:
        if len(m.end_to_end(cell)) < 2:
            out.append(f"cell {cell} reports no end-to-end metric besides setup_s")
        if not m.per_layer(cell):
            out.append(f"cell {cell} reports no per-layer metric")
    return out
