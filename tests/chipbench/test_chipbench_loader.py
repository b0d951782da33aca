"""BENCHMARK.json against its contract, and the loader that finds a cell's
files by name — a cell, configuration, mix and per-layer metric are each
added as files plus one entry, with no code change (no JAX)."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader  # noqa: E402

BENCH = loader.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|experts_per_tok")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16 and len(BENCH["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for word in BENCH["command"]:
        assert ONE_LINE.match(word) and not word.startswith("/") and ".." not in word
    assert any(w.startswith(tuple(p + "/" for p in BENCH["paths"])) for w in BENCH["command"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert ONE_LINE.match(cfg["why"]) and ONE_LINE.match(cfg["source"])
    assert cfg["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        loader.check_name(key, "reduced key")
        assert not WIDTHS.search(key), f"{key} is a width and may never be reduced"
    body = loader.read_json(os.path.join(ROOT, cfg["file"]))
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    assert {"serve", "chips", "assumed", "stands_for", "rehearsal"} <= set(body)
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and ONE_LINE.match(cell["why"])
    loader.check_name(cell["traffic"], "traffic")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    per_layer = m in BENCH["per_layer"]
    want = {"name", "unit", "better", "source"} | ({"layer", "moves"} if per_layer else {"bound"})
    assert want <= set(m) <= want | {"workloads"}
    loader.check_name(m["name"]), loader.check_unit(m["unit"])
    if per_layer:
        assert ONE_LINE.match(m["layer"])
        e2e = {e["name"]: e for e in BENCH["end_to_end"]}
        assert m["moves"] in e2e
        moved_in = e2e[m["moves"]].get("workloads", CELLS)
        assert set(m.get("workloads", CELLS)) <= set(moved_in)
    else:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(loader.data_file("end_to_end", m["name"]))
    for w in m.get("workloads", []):
        assert w in CELLS


def test_names_are_unique_and_setup_is_there():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and "setup_s" in names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_and_reports_enough(name):
    cell = loader.load_cell(name)
    e2e = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    assert cell["mix"]["loop"] in ("open", "closed")
    key = "rate_rps" if cell["mix"]["loop"] == "open" else "clients"
    assert cell["params"][key] > 0
    for spec in cell["per_layer"]:
        loader.load_reader(spec["reader"])
    serve = cell["config"]["serve"]
    longest = cell["mix"]["prompt"]["max"] + cell["mix"]["output"]["max"]
    assert longest <= serve["max_model_len"], "the mix's longest request must fit the deployment"


@pytest.mark.parametrize("bad", ["", "a b", "a/b", "x,y", "µs", "-lead", "n" * 65, None, 3])
def test_a_name_outside_the_allowed_characters_is_rejected(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_name(bad)


@pytest.mark.parametrize("bad", ["", "tokens per s", "µs", "u" * 17, "a,b", None])
def test_a_unit_outside_the_allowed_characters_is_rejected(bad):
    with pytest.raises(loader.BenchmarkError):
        loader.check_unit(bad)


@pytest.mark.parametrize("good", ["tokens/s", "%", "ms", "rows", "GB/s", "us"])
def test_units_in_use_are_allowed(good):
    assert loader.check_unit(good) == good


def test_unknown_names_are_errors_that_say_what_is_missing():
    with pytest.raises(loader.BenchmarkError, match="no workload"):
        loader.load_cell("no-such.cell")
    with pytest.raises(loader.BenchmarkError, match="no reader module"):
        loader.load_reader("no_such_reader")
    with pytest.raises(loader.BenchmarkError, match="peaks.json"):
        loader.load_peaks("TPU v9 imaginary")
    assert loader.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_adding_files_and_entries_adds_a_cell_with_no_code_change(tmp_path):
    """A later PR's whole change, made in a copy: one configuration, one mix,
    one cell and one per-layer metric, as new files and new entries."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cb = os.path.join(root, "chipbench")
    before = {os.path.join(dp, p): os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(cb) for p in fs}

    def put(rel, obj):
        with open(os.path.join(cb, rel), "w") as f:
            json.dump(obj, f)

    base = loader.read_json(os.path.join(ROOT, "chipbench", "configs", "qwen2.5-7b.json"))
    put("configs/other-7b.json", dict(base, name="other-7b", source="https://example.org/other"))
    put("traffic/agent-prefix.json", {
        "loop": "open", "arrivals": "burst", "burst": {"on_s": 2, "off_s": 4},
        "prompt": {"dist": "uniform", "min": 2100, "max": 2300},
        "output": {"dist": "fixed", "value": 64},
        "sharing": {"kind": "shared_prefix", "groups": 16, "prefix_len": 2048}})
    put("cells/other-7b.agent-prefix.json", {"rate_rps": 3.0})
    put("layer_metrics/unified_rows_per_dispatch.json", {
        "name": "unified_rows_per_dispatch", "unit": "rows", "layer": "engine loop",
        "moves": "ttft_ms_p50", "reader": "tokens_per_dispatch",
        "args": {"series": "dynamo_tpu_engine_dispatch_window_dispatches",
                 "labels": {"kind": "unified"}}})
    bench = loader.read_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "other-7b", "source": "https://example.org/other",
                             "file": "chipbench/configs/other-7b.json", "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "other-7b.agent-prefix", "config": "other-7b",
                               "traffic": "agent-prefix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "unified_rows_per_dispatch", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "engine loop", "moves": "ttft_ms_p50",
                               "workloads": ["other-7b.agent-prefix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = loader.load_cell("other-7b.agent-prefix", root)
    assert cell["config"]["name"] == "other-7b" and cell["params"]["rate_rps"] == 3.0
    assert cell["mix"]["sharing"]["kind"] == "shared_prefix"
    assert "unified_rows_per_dispatch" in [m["name"] for m in cell["per_layer"]]
    assert "device_idle_share.saturated" not in [m["name"] for m in cell["per_layer"]]
    assert "output_tokens_per_s" not in [m["name"] for m in cell["end_to_end"]]
    # the new mix needs no new generator code
    from chipbench import traffic

    phase = traffic.build_phase(cell["mix"], cell["params"], 5, 12.0, 152064)
    assert len(phase["requests"]) > 20
    assert len(phase["requests"]) > 16
    assert phase["requests"][0]["prompt"][:2048] == phase["requests"][16]["prompt"][:2048]
    # the old cells still load, and no file that was there was touched
    assert loader.load_cell(CELLS[0], root)["name"] == CELLS[0]
    for dp, _, fs in os.walk(cb):
        for p in fs:
            if os.path.join(dp, p) in before:
                assert os.path.getmtime(os.path.join(dp, p)) == before[os.path.join(dp, p)]


def test_a_layer_metric_file_that_disagrees_with_its_entry_is_refused(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = os.path.join(root, "chipbench", "layer_metrics", "prefix_hit_rate.json")
    spec = loader.read_json(path)
    spec["unit"] = "ratio"
    with open(path, "w") as f:
        json.dump(spec, f)
    with pytest.raises(loader.BenchmarkError, match="differs"):
        loader.load_cell(CELLS[0], root)
