"""The one command of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
traced, ``breakdown``), then ``info`` and, last, ``compared``: every
number that decided ``correct`` beside its limit. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. Needs a TPU with as many chips as the cell asks for:
anything else is a non-zero exit and no result.
"""

import time

_STARTED_AT = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(_BENCH_DIR)
OUT_DIRNAME = "perfbench_out"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, _CHECKOUT)
    from perfbench import manifest

    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)

    # Fails here in a directory that holds the benchmark alone.
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_CHECKOUT)
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"perfbench: JAX's backend is {backend!r}, not 'tpu': no "
            f"accelerator, no result.",
            file=sys.stderr,
        )
        return 1
    devices = jax.devices()
    if len(devices) < cell.chips:
        print(
            f"perfbench: {cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}: no result.",
            file=sys.stderr,
        )
        return 1

    from perfbench import harness

    line = harness.run_cell(
        cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        devices=devices[: cell.chips],
        started_at=_STARTED_AT,
        out_dir=os.path.join(_CHECKOUT, OUT_DIRNAME),
    )
    harness.print_result(line)
    # An untraced run that lacks one of its end-to-end metrics has failed.
    if not args.trace and len(line["metrics"]) < len(cell.end_to_end):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
