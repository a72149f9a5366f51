"""python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``; the last line of standard output is
the result as one JSON object.  Exits non-zero and prints no result where the
machine has no TPU or fewer chips than the cell asks for, where the device
kind has no entry in ``harness/peaks.json``, and where the program is not in
the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import time


def main() -> int:
    t_start = time.time()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    # the worker processes import the loop by name
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (root, os.environ.get("PYTHONPATH")) if p)
    # the runtime's session files: under this run's TMPDIR, never a fixed path
    os.environ.setdefault(
        "RAY_TPU_TMPDIR", os.path.join(tempfile.gettempdir(), "ray_tpu"))
    try:
        from ray_tpu.accelerators import tpu_manager
    except ImportError as e:
        print(f"perfbench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    from perfbench.harness import driver, manifest, procs

    cell = manifest.cell(args.workload)
    chips = tpu_manager().get_current_node_num_accelerators()
    if chips < cell.chips:
        print(f"perfbench: {cell.name} needs {cell.chips} TPU chip(s), this "
              f"host exposes {chips}; nothing was run", file=sys.stderr)
        return 1
    # whatever the runtime starts from here on is stopped and waited for
    # before this process ends, on every path out (harness/procs.py)
    procs.adopt_orphans()
    signal.signal(signal.SIGTERM, _terminated)
    line = None
    try:
        measured = driver.run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace), t_start)
        if driver.backend_initialized():
            raise driver.Refused("this process initialized a JAX backend")
        line = driver.result_line(cell, measured, bool(args.trace))
    except driver.Refused as e:
        print(f"perfbench: refused: {e}", file=sys.stderr)
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)   # not twice
        t_stop = time.time()
        try:
            strays = procs.stop_all()
        except TimeoutError as e:
            print(f"perfbench: a process outlived the run: {e}",
                  file=sys.stderr)
            line = None
        else:
            if line is not None:
                # read by people, not by the driver
                line["strays_stopped"] = len(strays)
                line["stop_all_s"] = time.time() - t_stop
            if strays:
                print(f"perfbench: {len(strays)} process(es) were still "
                      "running after ray_tpu.shutdown(); stopped and waited "
                      "for:\n  "
                      + "\n  ".join(c[:160] for c in strays), file=sys.stderr)
    if line is None:
        return 1
    print(json.dumps(line), flush=True)
    return 0


def _terminated(signum, frame):
    """SIGTERM ends the run as an exception, so that every ``finally`` on the
    way out runs: the runtime's shutdown, then ``procs.stop_all``."""
    raise SystemExit(128 + signum)


if __name__ == "__main__":
    sys.exit(main())
