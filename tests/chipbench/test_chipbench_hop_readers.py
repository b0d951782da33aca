"""The two readers of the server's per-request hop account on a hand-written
pair of scrapes, and each hop metric's file against its BENCHMARK.json entry
(no JAX)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext  # noqa: E402
from chipbench.readers import prom_mean_delta, ttft_outside_server  # noqa: E402

SUM, COUNT = "dynamo_tpu_request_hop_seconds_sum", "dynamo_tpu_request_hop_seconds_count"
BEFORE = f"""
# TYPE {SUM} counter
{SUM}{{hop="queue_wait"}} 1.5
{SUM}{{hop="server_ttft"}} 10.0
{SUM}{{hop="join_wait"}} 0.25
{COUNT}{{hop="queue_wait"}} 10
{COUNT}{{hop="server_ttft"}} 10
{COUNT}{{hop="join_wait"}} 3
"""
AFTER = f"""
{SUM}{{hop="queue_wait"}} 2.5
{SUM}{{hop="server_ttft"}} 19.0
{SUM}{{hop="join_wait"}} 0.25
{SUM}{{hop="edge_emit"}} 0.6
{COUNT}{{hop="queue_wait"}} 20
{COUNT}{{hop="server_ttft"}} 20
{COUNT}{{hop="join_wait"}} 3
{COUNT}{{hop="edge_emit"}} 4
"""
BENCH = loader.load_benchmark()
HOP_METRICS = [m for m in BENCH["per_layer"]
               if m["name"].startswith(("ttft_", "tpot_")) and m["name"].endswith("_ms")]


def _ctx(ttft_s=(1.0, 0.9, 1.1)):
    return {"before": promtext.parse(BEFORE), "after": promtext.parse(AFTER),
            "window": {"ttft_s": list(ttft_s)}}


def _args(hop, **more):
    return dict(sum_series=SUM, count_series=COUNT, labels={"hop": hop}, **more)


def test_mean_is_growth_of_the_sum_over_growth_of_the_count():
    assert prom_mean_delta.read(_ctx(), **_args("queue_wait", scale=1000.0)) == pytest.approx(100.0)
    assert prom_mean_delta.read(_ctx(), **_args("server_ttft")) == pytest.approx(0.9)


def test_a_count_that_did_not_grow_reads_nothing():
    assert prom_mean_delta.read(_ctx(), **_args("join_wait", scale=1000.0)) is None


def test_a_series_absent_before_counts_from_zero_and_one_absent_after_reads_nothing():
    assert prom_mean_delta.read(_ctx(), **_args("edge_emit", scale=1000.0)) == pytest.approx(150.0)
    assert prom_mean_delta.read(_ctx(), **_args("no_such_hop")) is None
    # the parent commit's server has no such series at all
    bare = {"before": {}, "after": {}, "window": {"ttft_s": [1.0]}}
    assert prom_mean_delta.read(bare, **_args("queue_wait")) is None
    assert ttft_outside_server.read(bare, **_args("server_ttft")) is None


def test_outside_server_is_the_generators_mean_less_the_servers():
    got = ttft_outside_server.read(_ctx(), **_args("server_ttft"))
    assert got == pytest.approx(1000.0 * (1.0 - 0.9))
    assert ttft_outside_server.read(_ctx(ttft_s=()), **_args("server_ttft")) is None


def test_the_account_adds_ten_metrics_to_every_cell():
    assert len(HOP_METRICS) == 10
    for cell in (w["name"] for w in BENCH["workloads"]):
        names = {m["name"] for m in loader.load_cell(cell)["per_layer"]}
        assert {m["name"] for m in HOP_METRICS} <= names


@pytest.mark.parametrize("m", HOP_METRICS, ids=lambda m: m["name"])
def test_hop_metric_file_matches_its_entry(m):
    spec = loader.read_json(loader.data_file("layer_metrics", m["name"]))
    assert spec["moves"] == m["moves"] and spec["layer"] == m["layer"]
    assert spec["unit"] == m["unit"] == "ms" and m["source"] == "program_counter"
    assert "MEAN" in spec["about"]
    reader = loader.load_reader(spec["reader"])
    hop = spec["args"]["labels"]["hop"]
    series = {SUM: 0.5, COUNT: 5}
    after = promtext.parse("\n".join(f'{s}{{hop="{hop}"}} {v}' for s, v in series.items()))
    value = reader.read({"before": {}, "after": after, "window": {"ttft_s": [0.25]}},
                        **spec["args"])
    want = 250.0 - 100.0 if spec["reader"] == "ttft_outside_server" else 100.0
    assert value == pytest.approx(want)
