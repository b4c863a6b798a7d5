#!/usr/bin/env python3
"""The spread study: run cells as the driver does and report, per cell and
end-to-end metric, two sets of runs with their medians and spreads.

    python3 benchmarks/chip/spread_study.py --cells a,b --sets 2 --runs 6 \
        --seconds 20 --traced 1 --out chiprun_out/spread

Every run is a process of its own with a seed of its own (the parent never
touches JAX, so the child gets the chip).  ``--traced n`` adds ``n`` runs with
``--trace 1`` per cell after the sets.  Results go to ``<out>/<cell>.jsonl``
(one line a run, with the tail of its standard error) and a summary is
printed: per metric the median and the spread (distance between the
quartiles over the median) of each set, the wider of the two, and the bound
that five times the widest would give.  Beside each ``setup_s`` it prints the
run's ``runtime_start_s`` (``device.runtime_start_s``: the backend's own start,
which ``setup_s`` leaves out since PR 54) and their sum, process start to window
start, so one study reads both definitions from the same runs; the three are
summarised with ``(max - min) / median`` beside the spread.  With
``--keep-traces`` the plain trace of each traced run is copied beside the results.
"""

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench.study import run_cell, span_over_median, spread, values_by_metric, with_old_setup  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, help="comma-separated cell names")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None, help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--seed-base", type=int, default=100)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "spread"))
    ap.add_argument("--keep-traces", action="store_true")
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--pending", default=None,
                    help="a pending/<configuration>.json: run its cells from an overlay that lists them")
    args = ap.parse_args()
    if args.pending:
        from chipbench.overlay import add_pending, make_overlay

        args.root = make_overlay(os.path.join(REPO, ".bench_data", "chip", "study_overlay"), REPO)
        add_pending(args.root, args.pending)
    bench = json.load(open(os.path.join(args.root, "BENCHMARK.json")))
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    failed = 0
    for cell in args.cells.split(","):
        sets: list[list[dict]] = []
        with open(os.path.join(args.out, cell + ".jsonl"), "a") as sink:
            def record(r: dict, label: str) -> None:
                nonlocal failed
                r["set"] = label
                sink.write(json.dumps(r) + "\n")
                sink.flush()
                ok = r["rc"] == 0 and r.get("correct") is True
                failed += 0 if ok else 1
                shown = {k: round(v["value"], 4) for k, v in with_old_setup(r)["metrics"].items()}
                print(f"{cell} {label} seed {r['seed']}: rc {r['rc']} correct {r.get('correct')}"
                      f" failed {r.get('failed')}/{r.get('attempted')} wall {r['wall_s']:.1f}s {shown}", flush=True)
                if r["log"]:  # where the set-up went: each stamp's seconds and first words
                    print("   stamps: " + " | ".join(f"{t:.2f} {' '.join(m.split()[:2])}" for t, m in r["log"]), flush=True)
                if not ok:
                    print(r["stderr_tail"][-1500:], flush=True)

            for s in range(args.sets):
                runs = []
                for i in range(args.runs):
                    seed = args.seed_base + 100 * s + i
                    r = run_cell(args.root, cell, seed=seed, seconds=seconds, trace=0)
                    record(r, f"set{s + 1}")
                    runs.append(r)
                sets.append(runs)
            for i in range(args.traced):
                r = run_cell(args.root, cell, seed=args.seed_base + 900 + i, seconds=seconds, trace=1)
                record(r, "traced")
                print(json.dumps({k: r.get(k) for k in ("metrics", "device", "breakdown")}), flush=True)
                plain = os.path.join(args.root, ".bench_data", "chip", "trace", cell, "plain.json.gz")
                if args.keep_traces and os.path.exists(plain):
                    shutil.copy(plain, os.path.join(args.out, f"{cell}.trace{i}.json.gz"))
        # the first run of a cell in a checkout builds its data and compiles:
        # its set-up is reported apart, as the driver does
        summaries = [values_by_metric([with_old_setup(r) for r in runs]) for runs in sets]
        names = sorted({n for s in summaries for n in s})
        set_up = ("setup_s", "runtime_start_s", "setup_s_with_runtime")
        print(f"== {cell}: {args.sets} set(s) of {args.runs} run(s), {seconds} s each")
        for name in names:
            parts, widest = [], 0.0
            for k, s in enumerate(summaries):
                if name in s:
                    vals = s[name]
                    if name in set_up and k == 0 and len(vals) > 1:
                        vals = vals[1:]
                    med, spr = spread(vals)
                    widest = max(widest, spr)
                    parts.append(f"set{k + 1} median {med:.6g} spread {100 * spr:.3f}%"
                                 + (f" (max-min)/median {100 * span_over_median(vals):.3f}%" if name in set_up else ""))
            print(f"   {name}: " + "; ".join(parts) + f"; wider {100 * widest:.3f}%, five times {100 * 5 * widest:.2f}%")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
