#!/usr/bin/env python3
"""The one-off rate sweep of an open-loop cell.

    python3 chipbench/sweep.py --workload W --rates 3,5,7,9,11 --seconds 25 --out PATH

One server (the cell's own deployment, started as run.py starts it), rising
fixed rates of the cell's mix, ``--seconds`` each, nothing in flight between
steps.  It stops at the first rate whose backlog grows: more requests in
flight at the end of the step than ``--max-in-flight`` (2 x max_batch by
default), or a mean TTFT in the second half of the step that is over
``--ttft-growth`` times the first half's and over ``--ttft-floor-ms``.  The
knee is the last rate before that.  The output is kept as
``chipbench/knees/<cell>.json``; the cell's rate (``cells/<cell>.json``) is
four fifths of the knee.  A sweep is a search, not a measurement: run.py never
searches.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import loader, run as harness  # noqa: E402


class Sweep(harness.Run):
    def job(self, results_path: str) -> dict:
        job = super().job(results_path)
        a = self.args
        job.update(mode="sweep", sweep={
            "rates": a.rate_list,
            "max_in_flight": a.max_in_flight or 2 * self.serve_flags["max_batch"],
            "ttft_growth": a.ttft_growth, "ttft_floor_ms": a.ttft_floor_ms,
        })
        return job

    def report(self, res: dict, marks: dict, tracing: dict, t_ready: float) -> dict:
        steps = res["steps"]
        sustained = [s["rate_rps"] for s in steps if not s["backlog_grows"] and not s["failed"]]
        return {
            "workload": self.cell["name"], "seed": self.args.seed,
            "seconds_per_rate": self.args.seconds, "device": self.device,
            "rehearsal": self.rehearse, "steps": steps,
            "knee_rps": max(sustained) if sustained else None,
            "stopped_at_rps": steps[-1]["rate_rps"] if steps[-1]["backlog_grows"] else None,
            "ready_s": t_ready - harness.T_PROCESS_START,
        }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests per second, rising")
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--max-in-flight", type=int, default=0)
    p.add_argument("--ttft-growth", type=float, default=2.0)
    p.add_argument("--ttft-floor-ms", type=float, default=1000.0)
    p.add_argument("--out", required=True)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    args.rate_list = [float(r) for r in args.rates.split(",")]
    args.trace = 0
    try:
        cell = loader.load_cell(args.workload)
    except loader.BenchmarkError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if cell["mix"]["loop"] != "open":
        print("chipbench: a sweep is for an open-loop cell", file=sys.stderr)
        return 2
    rc, line = harness.execute(Sweep(args, cell))
    if rc == 0:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(line, f, indent=1)
            f.write("\n")
        print(json.dumps({k: line[k] for k in ("workload", "knee_rps", "stopped_at_rps")}))
    return rc


if __name__ == "__main__":
    sys.exit(main())
