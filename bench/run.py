"""The benchmark's one command: one run of one cell on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are in ``BENCHMARK.json``; each cell's files are
found by name (``bench/spec.py``).  ``--trace 0`` prints the cell's
end-to-end metrics, taken over the whole window with the profiler off;
``--trace 1`` traces the window and prints its per-layer metrics, the
device's busy and window seconds and a breakdown.  Both decide ``correct``
the same way (``bench/check.py``).  The last line of stdout is the result
object; the numbers compared, each with its limit, are the last lines of
stderr.

It needs a TPU with at least the cell's chips and the program (``src/``)
beside it; without either it exits non-zero and prints no result.  JAX's
persistent compilation cache is kept in ``.jax_cache`` inside the checkout,
so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv) -> int:
    args = parse(argv)
    # the script's own directory would shadow modules of the standard library
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent]
    from bench import spec

    cell = spec.load(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}; nothing was run",
              file=sys.stderr)
        return 4
    spec.use_checkout_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s); nothing was run",
              file=sys.stderr)
        return 3
    from bench import cell as bcell, check

    result = bcell.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, OUT)
    for line in check.lines(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
