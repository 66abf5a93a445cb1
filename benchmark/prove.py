"""N fresh-process runs of one cell in a row, in ONE chip call, sharing the
compile cache; every run's last line goes to the output directory.

    chiprun -- python3 benchmark/prove.py --workload <name> --runs 6 \
        [--sets 2] [--seed0 1000003] [--seconds S] [--traced 1]

Each set uses the same seeds (seed0, seed0 + 1, ...). This parent never
touches JAX: the chip belongs to the run. Output:
chiprun_out/prove/<workload>.jsonl (one result per line, with set, seed,
exit code and wall) and <workload>.log (the runs' earlier lines).
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402  (no JAX behind it)


def one_run(workload, seed, seconds, trace, log):
    cmd = [sys.executable, os.path.join("benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed), "--trace",
           str(trace)]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE, text=True)
    wall = time.monotonic() - t0
    lines = p.stdout.strip().splitlines()
    log.write(f"\n===== {' '.join(cmd)} -> exit {p.returncode} in "
              f"{wall:.1f} s\n")
    log.write("\n".join(lines[:-1][-40:]) + "\n")
    if p.returncode != 0:
        log.write("--- stderr (tail)\n" + p.stderr[-4000:] + "\n")
    log.flush()
    try:
        result = json.loads(lines[-1]) if p.returncode == 0 else None
    except (IndexError, ValueError):
        result = None
    return {"rc": p.returncode, "wall_s": wall, "result": result}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seed0", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs to add after the sets")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "prove"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    base = os.path.join(args.out, args.workload)
    rows = []
    with open(base + ".log", "a") as log, open(base + ".jsonl", "a") as out:
        plan = [(s, args.seed0 + i, 0) for s in range(args.sets)
                for i in range(args.runs)]
        plan += [("traced", args.seed0 + i, 1) for i in range(args.traced)]
        for set_id, seed, trace in plan:
            r = one_run(args.workload, seed, args.seconds, trace, log)
            row = {"set": set_id, "seed": seed, "trace": trace, **r}
            rows.append(row)
            out.write(json.dumps(row) + "\n")
            out.flush()
            m = (r["result"] or {}).get("metrics", {})
            print(f"[prove] set {set_id} seed {seed} trace {trace} rc "
                  f"{r['rc']} wall {r['wall_s']:.1f} s correct "
                  f"{(r['result'] or {}).get('correct')} " + " ".join(
                      f"{k}={v['value']:.6g}" for k, v in m.items()),
                  flush=True)
            if r["rc"] != 0:
                print("[prove] a run failed: stopping (see the .log)",
                      flush=True)
                break
    # spread per set and metric: (Q3 - Q1) / median, statistics.quantiles
    for s in range(args.sets):
        res = [r["result"] for r in rows if r["set"] == s and r["result"]]
        if len(res) < 3:
            continue
        for name in res[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in res]
            first = vals[0] if name == "setup_s" else None
            if first is not None and s == 0:
                vals = vals[1:]      # the cell's first run here compiles
            print(f"[prove] set {s} {name}: median "
                  f"{statistics.median(vals):.6g} spread "
                  f"{100 * stats.spread(vals):.3f}% min {min(vals):.6g} "
                  f"max {max(vals):.6g} n {len(vals)}", flush=True)
    bad = [r for r in rows if r["rc"] != 0 or not r["result"]
           or not r["result"]["correct"]]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
