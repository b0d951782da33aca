"""Find a cell's files by the names BENCHMARK.json gives.

Nothing here lists a configuration, a mix, a cell or a metric: a later PR
adds ``configs/<config>.json``, ``traffic/<mix>.json``, ``cells/<cell>.json``,
``layer_metrics/<metric>.json`` (and, for a new kind of reading, a module
under ``readers/``) plus one entry in BENCHMARK.json, and edits no file.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def check_name(name, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(f"{what} {name!r}: letters, digits, '_', '.', '-' only (at most 64)")
    return name


def check_unit(unit) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchmarkError(f"unit {unit!r}: 1-16 of letters, digits, '_', '/', '%', '.', '-'")
    return unit


def read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {os.path.relpath(path, ROOT)}") from None
    except json.JSONDecodeError as e:
        raise BenchmarkError(f"{os.path.relpath(path, ROOT)}: {e}") from None


def data_file(kind: str, name: str, base: str = HERE) -> str:
    """``<base>/<kind>/<name>.json`` — the one place a name becomes a path."""
    return os.path.join(base, kind, check_name(name, kind) + ".json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str = ROOT) -> dict:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench.get(section, []):
            check_name(entry.get("name"), f"{section} name")
    for entry in bench.get("end_to_end", []) + bench.get("per_layer", []):
        check_unit(entry.get("unit"))
        if entry.get("source") not in SOURCES:
            raise BenchmarkError(f"metric {entry['name']}: source {entry.get('source')!r}")
        if entry.get("better") not in ("lower", "higher"):
            raise BenchmarkError(f"metric {entry['name']}: better {entry.get('better')!r}")
    return bench


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one run needs, found by name: the cell's entry, its
    configuration, mix and parameters, and the metrics it reports."""
    bench = load_benchmark(root)
    base = os.path.join(root, "chipbench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchmarkError(f"cell {name}: no configuration {cell['config']!r}")
    check_name(cell["traffic"], "traffic")
    config = read_json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = read_json(data_file("traffic", cell["traffic"], base))
    params = read_json(data_file("cells", name, base))
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = []
    for m in bench["per_layer"]:
        if not _applies(m, name):
            continue
        spec = read_json(data_file("layer_metrics", m["name"], base))
        for key in ("name", "unit", "layer", "moves"):
            if spec.get(key) != m.get(key):
                raise BenchmarkError(
                    f"layer_metrics/{m['name']}.json: {key} {spec.get(key)!r} "
                    f"differs from BENCHMARK.json's {m.get(key)!r}"
                )
        check_name(spec.get("reader"), "reader")
        per_layer.append(spec)
    return {
        "name": name, "cell": cell, "config": config, "mix": mix, "params": params,
        "end_to_end": end_to_end, "per_layer": per_layer,
        "run_seconds": bench["run_seconds"],
    }


def load_reader(name: str):
    """The module ``chipbench.readers.<name>``; it has ``read(ctx, **args)``."""
    check_name(name, "reader")
    if "." in name or "-" in name:
        raise BenchmarkError(f"reader {name!r} is not a module name")
    try:
        mod = importlib.import_module(f"chipbench.readers.{name}")
    except ModuleNotFoundError as e:
        raise BenchmarkError(f"no reader module chipbench/readers/{name}.py ({e})") from None
    if not callable(getattr(mod, "read", None)):
        raise BenchmarkError(f"chipbench/readers/{name}.py has no read(ctx, **args)")
    return mod


def load_peaks(device_kind: str) -> dict:
    """Published peaks of the device; an unknown ``device_kind`` is an error."""
    table = read_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchmarkError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json ({sorted(table)})"
        )
    return table[device_kind]
