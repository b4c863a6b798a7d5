#!/usr/bin/env python3
"""Find the ANN endpoint's knee, once, on the chip.

    python3 benchmarks/chip/sweep_knee.py --out chiprun_out/knee

Capacity is the median ``ann_qps`` of the closed-loop cell (512 in flight,
full batches).  The open-loop cell's mix is then offered at a few fixed
fractions of it, each as a cell added to a temporary overlay of the benchmark
(added files only), and the script prints answer times and failures at each
rate.  The knee is the highest rate at which nothing fails and the tail stays
within a few dispatches; ``open_steady``'s rate is four fifths of capacity,
rounded to two digits, written into ``workloads/ann_laion_clip512.open_steady
.json`` by hand.  A later benchmark PR runs this again after the plane changes.
"""

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench.overlay import add_cell, add_pending, make_overlay  # noqa: E402
from chipbench.study import run_cell  # noqa: E402


def two_digits(x: float) -> float:
    return float(f"{x:.2g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--closed", default="ann_laion_clip512.batch_closed")
    ap.add_argument("--open", default="ann_laion_clip512.open_steady")
    ap.add_argument("--fractions", default="0.6,0.8,0.9,1.0,1.1")
    ap.add_argument("--capacity-runs", type=int, default=3)
    ap.add_argument("--capacity", type=float, default=None, help="skip the closed-loop runs")
    ap.add_argument("--capacity-from", default=None,
                    help="skip them and take the median ann_qps of a spread study's .jsonl")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "knee"))
    ap.add_argument("--pending", default=os.path.join(HERE, "pending", "ann_laion_clip512.json"),
                    help="entries to merge into the overlay when BENCHMARK.json does not list the cells yet")
    args = ap.parse_args()
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    sink = open(os.path.join(args.out, "knee.jsonl"), "a")
    overlay = make_overlay(os.path.join(REPO, ".bench_data", "chip", "knee_overlay"), REPO)
    if not any(w["name"] == args.open for w in bench["workloads"]):
        add_pending(overlay, args.pending)

    if args.capacity_from is not None:
        runs = [json.loads(line) for line in open(args.capacity_from)]
        args.capacity = statistics.median(
            r["metrics"]["ann_qps"]["value"] for r in runs if r.get("trace") == 0 and r.get("metrics")
        )
    if args.capacity is None:
        rates = []
        for i in range(args.capacity_runs):
            r = run_cell(overlay, args.closed, seed=700 + i, seconds=seconds, trace=0)
            sink.write(json.dumps(r) + "\n")
            sink.flush()
            if r["rc"] != 0:
                print(r["stderr_tail"][-1500:])
                return 1
            rates.append(r["metrics"]["ann_qps"]["value"])
            print(f"closed loop seed {700 + i}: {rates[-1]:.1f} queries/s", flush=True)
        capacity = statistics.median(rates)
    else:
        capacity = args.capacity
    print(f"capacity {capacity:.1f} queries/s; four fifths, two digits: {two_digits(0.8 * capacity)}")

    base = json.load(open(os.path.join(HERE, "workloads", args.open + ".json")))
    print("rate  fraction  attempted  failed  p99_ms  recall  wall_s")
    for k, fraction in enumerate(float(f) for f in args.fractions.split(",")):
        rate = two_digits(fraction * capacity)
        name = f"{args.open.rsplit('.', 1)[0]}.sweep_{k}"
        add_cell(overlay, name=name, like=args.open,
                 workload=dict(base, traffic=f"sweep_{k}", rate_per_s=rate))
        r = run_cell(overlay, name, seed=800 + k, seconds=seconds, trace=0)
        sink.write(json.dumps(dict(r, rate_per_s=rate, fraction=fraction)) + "\n")
        sink.flush()
        if r["rc"] != 0:
            print(f"{rate:g}  {fraction}  run failed rc {r['rc']}\n{r['stderr_tail'][-1500:]}")
            continue
        m = r["metrics"]
        print(f"{rate:g}  {fraction}  {r['attempted']}  {r['failed']}  {m['ann_p99_ms']['value']:.0f}"
              f"  {m['ann_recall10']['value']:.4f}  {r['wall_s']:.0f}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
