"""Back-to-back runs of one cell from one checkout on one machine: the
situation in which a run's verdict can come to depend on an earlier run.

    chiprun --timeout 2400 -- python3 perfbench/soak.py \\
        --workload gpt3-6.7b.save_in_loop --seeds 0-13 --seconds 30

Every run is the benchmark's own command in a new process (this parent
never touches JAX, so it never holds the chip). Prints each run's result
line, then the spread of every metric as the contract reckons it: the
distance between the quartiles (``statistics.quantiles(n=4)``) as a
share of the median. Exits non-zero unless every run said
``correct: true``. ``--out`` names a file under ``chiprun_out/`` for the
lines.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(_BENCH_DIR)


def parse_seeds(text: str) -> list:
    seeds = []
    for part in text.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            seeds.extend(range(int(lo), int(hi) + 1))
        else:
            seeds.append(int(part))
    return seeds


def spread(values: list):
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else None


def _text(captured) -> str:
    if captured is None:
        return ""
    return captured if isinstance(captured, str) else captured.decode(errors="replace")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="e.g. 0-13 or 3,5,2147483653")
    parser.add_argument("--seconds", type=int, required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out", default=None)
    parser.add_argument(
        "--run-timeout", type=int, default=1200,
        help="seconds a run may take (the contract's allowance for a cold one)",
    )
    args = parser.parse_args(argv)

    with open(os.path.join(_CHECKOUT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    lines = []
    out_path = args.out and os.path.join(_CHECKOUT, "chiprun_out", args.out)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for seed in parse_seeds(args.seeds):
        begin = time.monotonic()
        try:
            done = subprocess.run(
                command
                + ["--workload", args.workload, "--seed", str(seed)]
                + ["--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=_CHECKOUT,
                capture_output=True,
                text=True,
                timeout=args.run_timeout,
            )
        except subprocess.TimeoutExpired as e:  # killed; what it said is kept
            done = subprocess.CompletedProcess(
                e.cmd, 124, _text(e.stdout), _text(e.stderr)
            )
        wall = time.monotonic() - begin
        out = done.stdout.strip().splitlines()
        try:
            line = json.loads(out[-1])
        except (IndexError, ValueError):
            line = {"correct": False, "no_result_line": True}
        line.update(seed=seed, rc=done.returncode, wall_s=round(wall, 1))
        lines.append(line)
        for earlier in out[:-1]:
            print(earlier)
        for mark in done.stderr.splitlines():
            if mark.startswith("[perfbench]") and " s  " in mark:
                print(mark)  # where the set-up's seconds went
        if done.returncode != 0 or not line.get("correct"):
            print(done.stderr[-4000:])
        print(json.dumps(line), flush=True)
        if out_path:
            with open(out_path, "w") as f:
                json.dump({"lines": lines}, f)

    good = [ln for ln in lines if ln.get("correct") and ln["rc"] == 0]
    summary = {"workload": args.workload, "runs": len(lines), "correct": len(good)}
    names = sorted({n for ln in good for n in ln.get("metrics", {})})
    for name in names:
        values = [ln["metrics"][name]["value"] for ln in good if name in ln["metrics"]]
        summary[name] = {
            "median": statistics.median(values),
            "min": min(values),
            "max": max(values),
            "spread": spread(values) if len(values) >= 2 else None,
            "values": values,
        }
    print("SOAK " + json.dumps(summary), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"summary": summary, "lines": lines}, f)
    return 0 if len(good) == len(lines) else 1


if __name__ == "__main__":
    sys.exit(main())
