"""python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once in this process and prints, as the
last line of standard output, the contract's JSON object. Exits non-zero
and prints no result without a TPU, with fewer chips than the cell asks
for, or where `paddle_tpu` is not beside it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="directory for the run's raw readings (per-chunk "
                         "times, per-second latencies): the noise study")
    ap.add_argument("--bench", default=None,
                    help="another BENCHMARK.json (a cell being proposed)")
    ap.add_argument("--override", default=None,
                    help='JSON file {"config": {...}, "traffic": {...}} of '
                         "keys to replace (sweeps and studies; the line "
                         "then says so)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="accept a non-TPU device (tests; the line then "
                         "says so)")
    args = ap.parse_args(argv)
    # a hang must not hold the chip past the driver's limit for a run
    faulthandler.dump_traceback_later(1150, exit=True)
    from chipbench import harness

    files = harness.Files(bench_path=args.bench)
    seconds = args.seconds
    if seconds is None:
        seconds = files.bench()["run_seconds"]
    override = None
    if args.override:
        with open(args.override) as f:
            override = json.load(f)
    try:
        line = harness.run_cell(args.workload, args.seed, seconds,
                                bool(args.trace), t_start=T_START,
                                rehearsal=args.rehearsal, override=override,
                                files=files,
                                dump=args.dump)
    except harness.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    del line              # the line carries the verdict (`correct`)
    faulthandler.cancel_dump_traceback_later()
    harness.leave(grace_s=15.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
