"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... [--control]

For each seed, in one process on the cell's chips: the program's readings
through the benchmark's own set-up (training: the first three steps;
serving: a whole run with a window of ``--seconds``) compared with the plain
reference, as a run compares them (the lower readings); with
``--control``, the reference at float8 put in the program's place, and
for training also the reference with half of each batch's tokens left
out of the loss, compared in the same way (the upper readings). One JSON
line per seed and kind on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
# nothing outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def train_readings(cell, seed, control):
    from bench.kinds import train as T
    sess, feed, shapes, key, tc = T.build(cell, seed)
    prog = T.first_steps(sess, shapes, key, tc.beta)
    sess.close()
    kept = feed.kept
    del sess, feed
    gc.collect()
    ref = T.reference(cell, seed, kept)
    out = [("program", T.compare(prog, ref, cell.limits))]
    if control:
        low = T.reference(cell, seed, kept, precision="float8")
        out.append(("control_float8", T.compare(low, ref, cell.limits)))
        half = T.reference(cell, seed, kept, fault="half_batch")
        out.append(("fault_half_batch", T.compare(half, ref, cell.limits)))
    return out


def serve_readings(cell, seed, control, seconds):
    """A run of the cell (set-up, fill and window as the benchmark makes
    them) with, under ``control``, the float8 reference's check too."""
    from bench import harness
    from bench.kinds import serve_closed as S
    out = S.run(cell, seed, seconds, False, t_start=harness.now(),
                control="float8" if control else None)
    rows = [("program", out["checks"])]
    if control:
        rows.append(("control_float8", out["control"]))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="serving: the window of each seed's run")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    from bench import harness, program
    cell = harness.find_cell(args.workload)
    program.import_program()
    harness.require_chips(cell.chips)
    harness.compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        if cell.traffic["kind"] == "train":
            rows = train_readings(cell, seed, args.control)
        else:
            rows = serve_readings(cell, seed, args.control, args.seconds)
        for kind, checks in rows:
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind,
                              "values": {c["name"]: c["value"]
                                         for c in checks},
                              "seconds": time.perf_counter() - t0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
