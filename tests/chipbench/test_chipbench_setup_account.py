"""The six metrics that read the start's account (PR 58): each file against
its BENCHMARK.json entry, the one new reader (``prom_value``) on hand-written
Prometheus text as ``llm/metrics.py`` prints the account, and what a cell
lists.  No JAX."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext  # noqa: E402
from chipbench.readers import prom_value  # noqa: E402

PHASE = "dynamo_tpu_engine_setup_phase_seconds"
MISSES = "dynamo_tpu_engine_compile_cache_misses"
BENCH = loader.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
# name: (unit, the series it reads, the labels it reads, what the fixture holds)
NEW = {
    "setup_import_s": ("s", PHASE, {"phase": "import"}, 3.25),
    "setup_build_params_s": ("s", PHASE, {"phase": "build:params"}, 6.5),
    "setup_warm_lower_s": ("s", PHASE, {"phase": "warm:lower"}, 7.75),
    "setup_warm_compile_s": ("s", PHASE, {"phase": "warm:compile"}, 4.125),
    "setup_warm_walk_s": ("s", PHASE, {"phase": "warm:walk"}, 21.5),
    "setup_programs_compiled_anew": ("count", MISSES, None, 9.0),
}
# The window's first scrape, as the engine block of /metrics prints the
# account of a start that compiled nine programs anew.
BEFORE = f"""# HELP {PHASE} Wall of one phase of the start
# TYPE {PHASE} gauge
{PHASE}{{phase="import"}} 3.25
{PHASE}{{phase="build:params"}} 6.5
{PHASE}{{phase="build:cache"}} 0.5
{PHASE}{{phase="build:calibrate"}} 0.0
{PHASE}{{phase="build:other"}} 1.25
{PHASE}{{phase="warm:lower"}} 7.75
{PHASE}{{phase="warm:compile"}} 4.125
{PHASE}{{phase="warm:walk"}} 21.5
{PHASE}{{phase="warm:sp"}} 0.0
{PHASE}{{phase="serve:listen"}} 0.125
# TYPE dynamo_tpu_engine_setup_seconds gauge
dynamo_tpu_engine_setup_seconds 45.0
# TYPE dynamo_tpu_engine_warmup_seconds gauge
dynamo_tpu_engine_warmup_seconds 33.375
# TYPE dynamo_tpu_engine_compile_cache_hits gauge
dynamo_tpu_engine_compile_cache_hits 2
# TYPE {MISSES} gauge
{MISSES} 9
# TYPE dynamo_tpu_engine_jax_compile_seconds_total counter
dynamo_tpu_engine_jax_compile_seconds_total{{stage="backend_compile"}} 130.5
"""
# The second scrape: a program compiled INSIDE the window (a fault that
# no_compile_in_window judges); the start's metrics must not move with it.
AFTER = BEFORE.replace(f"{MISSES} 9", f"{MISSES} 10").replace(
    '{phase="warm:walk"} 21.5', '{phase="warm:walk"} 99.0')


def _ctx(before=BEFORE, after=AFTER):
    return {"before": promtext.parse(before), "after": promtext.parse(after)}


def _spec(name):
    return loader.read_json(loader.data_file("layer_metrics", name))


def test_six_entries_are_appended_to_per_layer():
    names = [m["name"] for m in BENCH["per_layer"]]
    # in their order, wherever a later PR's entries leave them (no index from the end)
    at = names.index(next(iter(NEW)))
    assert names[at:at + len(NEW)] == list(NEW)
    assert len(names) == len(set(names))
    # they stand under setup_s, and setup_s is judged everywhere
    assert set(NEW) <= {m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s"}
    assert "workloads" not in next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")


@pytest.mark.parametrize("name", list(NEW))
def test_metric_file_matches_its_entry_key_for_key(name):
    unit, series, labels, _ = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_counter",
                     "layer": "engine start", "moves": "setup_s"}
    spec = _spec(name)
    assert set(spec) == {"name", "unit", "layer", "moves", "reader", "args", "about"}
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"], spec["reader"]) == (
        name, unit, "engine start", "setup_s", "prom_value")
    assert spec["args"] == ({"series": series, "labels": labels} if labels else {"series": series})
    assert series in spec["about"] and len(spec["about"]) > 80
    loader.load_reader(spec["reader"])


@pytest.mark.parametrize("cell", CELLS)
def test_load_cell_of_every_cell_lists_the_six(cell):
    loaded = loader.load_cell(cell)
    assert [m["name"] for m in loaded["per_layer"] if m["name"] in NEW] == list(NEW)
    assert "setup_s" in {m["name"] for m in loaded["end_to_end"]}


@pytest.mark.parametrize("name", list(NEW))
def test_a_fixture_scrape_reads_the_value_at_the_windows_first_scrape(name):
    expected = NEW[name][3]
    spec = _spec(name)
    read = loader.load_reader(spec["reader"]).read
    assert read(_ctx(), **spec["args"]) == expected
    # a program without the account (the parent's side): nothing, and no fault
    assert read({"before": {}, "after": {}}, **spec["args"]) is None
    assert read(_ctx(before="# nothing yet\n"), **spec["args"]) is None


def test_prom_value_reads_before_and_never_after():
    args = {"series": PHASE, "labels": {"phase": "warm:walk"}}
    assert prom_value.read(_ctx(), **args) == 21.5
    assert prom_value.read(_ctx(before=AFTER), **args) == 99.0
    assert prom_value.read(_ctx(), series=MISSES) == 9.0
    assert prom_value.read(_ctx(), series=MISSES, scale=0.5) == 4.5
    # a value of zero is a reading (a warm start compiled nothing anew)
    warm = BEFORE.replace(f"{MISSES} 9", f"{MISSES} 0")
    assert prom_value.read(_ctx(before=warm), series=MISSES) == 0.0
    assert prom_value.read(_ctx(), series=PHASE, labels={"phase": "warm:sp"}) == 0.0


def test_prom_value_sums_nothing_it_should_not():
    # every phase matches a bare name: a sum there would read as one phase
    assert promtext.value(promtext.parse(BEFORE), PHASE) == 45.0
    assert prom_value.read(_ctx(), series=PHASE) is None
    assert prom_value.read(_ctx(), series=PHASE, labels={}) is None
    # a label the series does not have, a value it does not have, a prefix
    assert prom_value.read(_ctx(), series=PHASE, labels={"stage": "import"}) is None
    assert prom_value.read(_ctx(), series=PHASE, labels={"phase": "warm"}) is None
    assert prom_value.read(_ctx(), series="dynamo_tpu_engine_setup_phase") is None
    assert prom_value.read(_ctx(), series="dynamo_tpu_engine_setup_seconds") == 45.0


def test_the_five_phases_and_the_others_add_up_to_setup_seconds():
    parsed = promtext.parse(BEFORE)
    read = {n: prom_value.read(_ctx(), **_spec(n)["args"]) for n in NEW if n.endswith("_s")}
    in_benchmark = {_spec(n)["args"]["labels"]["phase"] for n in read}
    others = sum(v for (name, ls), v in parsed.items()
                 if name == PHASE and dict(ls)["phase"] not in in_benchmark)
    assert sum(read.values()) + others == promtext.value(parsed, "dynamo_tpu_engine_setup_seconds")
    warm = sum(v for (name, ls), v in parsed.items()
               if name == PHASE and dict(ls)["phase"].startswith("warm:"))
    assert warm == promtext.value(parsed, "dynamo_tpu_engine_warmup_seconds")
