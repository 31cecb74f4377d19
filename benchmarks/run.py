"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * uc1_fig5..9, uc2_fig11, uc3_fig12/13 — total pipeline wall time per
    (protocol x failure plan); derived = overhead %% vs the no-recovery
    execution baseline (the paper's Figures 5-9/11-13).
  * lineage_fig10 — lineage-capture overhead vs plain LOG.io (<1.5% claim).
  * process — thread vs process execution mode + recovery latency
    (``benchmarks/process_mode.py``).

Usage: PYTHONPATH=src python -m benchmarks.run [--full] [--repeats N]
                                  [--only uc1,lineage] [--json FILE]
"""
import argparse
import json


def main():
    import inspect

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale repeats + the largest configurations")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke scale for the sections that support it "
                         "(process/transport)")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--only", default=None,
                    help="comma list: uc1,uc2,uc3,lineage,lineage_query,"
                         "logstore,batching,controller,process")
    ap.add_argument("--json", default=None,
                    help="also write the collected rows as JSON "
                         "(per-commit perf-trajectory artifact)")
    args = ap.parse_args()
    repeats = args.repeats or (3 if args.full else (1 if args.quick else 2))
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (batching, controller, lineage_overhead,
                            lineage_query, logstore_throughput, process_mode,
                            uc1, uc2, uc3)
    rows = []
    print("name,us_per_call,derived")
    for name, mod in (("uc1", uc1), ("uc2", uc2), ("uc3", uc3),
                      ("lineage", lineage_overhead),
                      ("lineage_query", lineage_query),
                      ("logstore", logstore_throughput),
                      ("batching", batching),
                      ("controller", controller),
                      ("process", process_mode)):
        if only and name not in only:
            continue
        kwargs = {"repeats": repeats, "full": args.full}
        if "quick" in inspect.signature(mod.run).parameters:
            kwargs["quick"] = args.quick
        try:
            mod.run(rows, **kwargs)
        except Exception as e:   # keep the suite going; record the failure
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            rows.append((f"{name}/ERROR", 0.0, f"{type(e).__name__}"))
    if args.json:
        with open(args.json, "w") as f:
            json.dump([{"name": n, "us_per_call": u, "derived": d}
                       for n, u, d in rows], f, indent=2)
        print(f"# wrote {len(rows)} rows to {args.json}", flush=True)
    return rows


if __name__ == '__main__':
    main()
