"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: refuses anything but the TPU chips the cell asks for before
any work, sets the program up from the seed, warms it, measures for
``--seconds``, checks what the timed path produced against the plain
reference, prints one JSON object as the last line and exits.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

harness.Clock.start = _T0


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the builder's own readings, never given by the driver
    p.add_argument("--control", default="none",
                   choices=("none", "reference_fp8", "program_int8"),
                   help="put the lower precision in the program's place")
    p.add_argument("--broken", default="",
                   help="break the timed path underneath (tests)")
    p.add_argument("--sync_every_step", type=int, default=0,
                   help="train: fetch every reading with nothing queued "
                        "ahead (the method the stall hunt compares with)")
    p.add_argument("--more_seeds", type=int, default=0,
                   help="serve: after the run, check this many more seeds "
                        "in the same process (outside any timed window)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    try:
        import dalle_pytorch_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this directory: {e}",
              file=sys.stderr)
        return 4
    device = harness.require_tpu(cell.chips)
    from dalle_pytorch_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    listener = harness.CompileListener()
    if cell.kind == "train":
        from benchmark import train_cell as driver
    elif cell.kind == "serve":
        from benchmark import serve_cell as driver
    else:
        raise SystemExit(f"unknown traffic kind {cell.kind!r}")
    line = driver.run(cell, args, device, listener)
    sys.stdout.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
