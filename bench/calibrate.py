"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--controls 3] [--program 0|1] [--out chiprun_out/calibrate.<cell>.json]

In one process, with one trainer built once (so the step compiles once):
for every seed, the program's first steps through ``Trainer.train()``
against the plain reference, as a run compares them (the lower readings);
for the first ``--controls`` seeds also the control, the reference in
float8 put in the program's place, and the faults the cell can have,
planted in the reference put in the program's place: half of the batch
left out (the mean over the rest), and on several chips the exchange left
out (each chip's gradient from its own rows alone).  A step that returns
its state unchanged reads 1 by ``update_gap``'s measure and needs no run.
``--program 0`` reads only the control and the faults, which need one chip
whatever the cell's.  Every reading goes to ``--out`` as JSON and stdout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def worst(prog, ref, n: int = 3) -> dict:
    """The leaves that set ``grad_gap`` and ``update_gap``, worst first."""
    from bench import check

    out = {}
    for name, p, r, keys in (
            ("grad", prog.grad_norms, ref.grad_norms, sorted(ref.grad_norms)),
            ("update", prog.change_norms, ref.change_norms, check.moved(ref))):
        g = check.leaf_gaps(p, r, keys)
        out[name] = [[k, g[k], p[k], r[k]] for k in sorted(g, key=g.get)[::-1][:n]]
    return out


def calibrate(cell, seeds, controls: int, program: bool = True) -> dict:
    """``program=False`` reads only the control and the faults against the
    reference: they are the reference's own arithmetic at the cell's batch,
    so one chip reads them for a cell of several."""
    import jax

    from bench import cell as bcell, check, traffic
    from bench.reference import train as rtrain
    from repro.data.pipeline import DataConfig, TokenPipeline

    s = bcell.build(cell, seeds[0]) if program else None
    B = cell.mix["global_batch"]
    variants = {"control_fp8": dict(precision="fp8")}
    if B > 1:  # a batch of one row has no half to leave out
        variants["fault_half_batch"] = dict(rows=slice(0, B // 2))
    if cell.chips > 1:
        variants["fault_no_exchange"] = dict(rows=slice(0, B // cell.chips))
    out = {"workload": cell.name, "device": bcell.device_summary(),
           "seeds": {}}
    for i, seed in enumerate(seeds):
        row = {}
        t0 = time.perf_counter()
        if program:
            s.pipeline.inner = TokenPipeline(s.arch, s.trainer.shape, DataConfig(
                seed=seed, **{k: cell.mix[k] for k in traffic.DATA_KEYS}))
            s.pipeline.kept.clear()
            params, opt, prog = bcell.drive_check_steps(s, seed)
            for x in jax.tree.leaves((params, opt)):
                x.delete()
        t1 = time.perf_counter()
        expected = bcell.reference_batches(cell, seed)
        ref = rtrain.run(cell.cfg, cell.workload["optimizer"], seed, expected)
        row.update(program_s=t1 - t0, reference_s=time.perf_counter() - t1,
                   reference_losses=ref.losses)
        if program:
            delivered = [s.pipeline.kept.get(t, {}) for t in range(len(expected))]
            row["program"] = {**check.gaps(prog, ref),
                              **check.batch_faults(delivered, expected),
                              "losses": prog.losses, "worst_leaves": worst(prog, ref)}
        if i < controls:
            for name, kw in variants.items():
                other = rtrain.run(cell.cfg, cell.workload["optimizer"], seed,
                                   expected, **kw)
                row[name] = {**check.gaps(other, ref), "losses": other.losses,
                             "worst_leaves": worst(other, ref)}
        out["seeds"][str(seed)] = row
        print(json.dumps({"seed": seed, **row}), flush=True)
    rows = out["seeds"].values()
    if program:
        out["lower"] = {k: max(r["program"][k] for r in rows) for k in check.GAP_NAMES}
    for name in variants:
        got = [r[name] for r in rows if name in r]
        out[f"upper_{name}"] = {k: min(g[k] for g in got) for k in check.GAP_NAMES}
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="0: read only the control and the faults")
    args = ap.parse_args(argv)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent]
    from bench import spec

    spec.use_checkout_cache()

    out = calibrate(spec.load(args.workload),
                    [int(x) for x in args.seeds.split(",")], args.controls,
                    bool(args.program))
    out["seconds"] = time.perf_counter() - T_START
    print(json.dumps({k: v for k, v in out.items() if k != "seeds"}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
