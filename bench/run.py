"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell's files are found by the names in
``BENCHMARK.json`` (see ``bench/harness.py``). The run makes its weights
and inputs from ``--seed``, warms every shape it uses (set-up), measures
for about ``--seconds``, checks what the timed path produced against the
plain reference in ``bench/reference``, and prints one JSON line as the
last line of standard output. With ``--trace 1`` the window is profiled
and the line carries the cell's per-layer metrics instead of its
end-to-end ones. Without the chips the cell asks for it prints no result
and exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
# nothing outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the profile to DIR and keep it (default: "
                         "a scratch directory in the checkout, removed once "
                         "reduced)")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    faulthandler.enable()

    from bench import harness, program
    cell = harness.find_cell(args.workload)
    program.import_program()
    harness.require_chips(cell.chips)
    cache = harness.compile_cache()
    harness.note(f"compile cache: {cache}")
    out = harness.traffic_kind(cell.traffic["kind"]).run(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        trace_dir=args.keep_trace or os.path.join(ROOT, ".bench_trace",
                                                  cell.name),
        keep_trace=args.keep_trace is not None)
    print(harness.result_line(**out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
