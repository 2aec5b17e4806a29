"""Runs one cell on the chip with a fault planted under the timed path
and exits 0 only if every such run says ``correct: false``.

    chiprun -- python3 perfbench/control.py --workload gpt3-6.7b.save_in_loop \\
        --fault lossy_save --seeds 3,4,5 --seconds 12

One process a seed (a process that has touched JAX holds the chip), each
the benchmark's own entry with ``faults.<fault>()`` around it.
"""

import argparse
import json
import os
import subprocess
import sys

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(_BENCH_DIR)
_RUN_TIMEOUT_S = 1200  # the contract's allowance for a run that compiles


def _one(args) -> int:
    sys.path.insert(0, _CHECKOUT)
    from perfbench import faults
    from perfbench import run as entry

    with faults.FAULTS[args.fault]():
        return entry.main(
            ["--workload", args.workload, "--seed", str(args.one)]
            + ["--seconds", str(args.seconds), "--trace", "0"]
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--fault", required=True)
    parser.add_argument("--seeds", default="3,4,5")
    parser.add_argument("--seconds", type=int, default=12)
    parser.add_argument("--one", type=int, default=None, help="internal: run this seed here")
    args = parser.parse_args(argv)
    if args.one is not None:
        return _one(args)

    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        try:
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__)]
                + ["--workload", args.workload, "--fault", args.fault]
                + ["--seconds", str(args.seconds), "--one", str(seed)],
                cwd=_CHECKOUT,
                capture_output=True,
                text=True,
                timeout=_RUN_TIMEOUT_S,
            )
        except subprocess.TimeoutExpired:
            # A control that gives no number has failed; it sets no reading.
            print(f"CONTROL {args.workload} {args.fault} seed {seed}: killed after {_RUN_TIMEOUT_S} s")
            continue
        out = done.stdout.strip().splitlines()
        try:
            line = json.loads(out[-1])
        except (IndexError, ValueError):
            line = None
        mismatches = [ln for ln in out[:-1] if "MISMATCH" in ln]
        print(
            f"CONTROL {args.workload} {args.fault} seed {seed}: rc {done.returncode}, "
            f"correct {line and line['correct']}, compared {line and line['compared']}, "
            f"{len(mismatches)} mismatch lines; first: "
            f"{mismatches[0][:400] if mismatches else None}",
            flush=True,
        )
        if line is None:
            print(done.stderr[-3000:])
        elif line["correct"] is False:
            caught += 1
    print(f"CONTROL {args.fault}: caught on {caught} of {len(seeds)} seeds", flush=True)
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
