"""The comparison that decides ``correct`` for a training cell.

The program's first steps, driven through ``Trainer.train`` in set-up, are
compared with the plain reference (``bench.reference.train``) from the same
seed's weights and batches:

* ``loss_gap``    the widest relative gap of a step's loss;
* ``grad_gap``    the worst leaf's gap between the norms of the first
                  gradient, program against reference, over the larger of
                  the reference's norm of that leaf and of the median leaf;
* ``update_gap``  the same for the norm of each leaf's change over the
                  compared steps.  Leaves whose reference gradient is under a
                  thousandth of the median leaf's (a key bias under softmax)
                  move under AdamW by round-off alone and are left out, by
                  that rule and not by name.

* ``grad_err``    the median leaf's error of the first gradient itself,
                  element by element at fixed sampled positions:
                  ``|g - g_ref| / |g_ref|``.  This one is a norm of a
                  difference: the first gradient is linear in the data and
                  the weights, so rounding moves it little and a lower
                  precision or a part of the batch left out moves it much,
                  where the norms above move little for either.

The other two compare norms and not the norm of a difference: under AdamW
a weight's first updates are about ``lr`` whichever way rounding tips its
gradient's sign.  Besides, every batch the program's pipeline delivered must
equal the benchmark's own (``bench.traffic``), and no two rows may repeat.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference.train import Readings

GAP_NAMES = ("loss_gap", "grad_gap", "update_gap", "grad_err")

#: leaves whose reference gradient norm is under this share of the median
#: leaf's are left out of ``update_gap``
STILL = 1e-3


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keys: Sequence[str]) -> Dict[str, float]:
    floor = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30) for k in keys}


def moved(ref: Readings) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding."""
    med = statistics.median(ref.grad_norms.values())
    return [k for k in sorted(ref.grad_norms) if ref.grad_norms[k] >= STILL * med]


def gaps(prog: Readings, ref: Readings) -> Dict[str, float]:
    if set(prog.grad_norms) != set(ref.grad_norms):
        raise ValueError(f"leaves differ: {sorted(set(prog.grad_norms) ^ set(ref.grad_norms))}")
    if len(prog.losses) != len(ref.losses):
        raise ValueError(f"{len(prog.losses)} losses against {len(ref.losses)}")
    loss = [abs(p - r) / abs(r) for p, r in zip(prog.losses, ref.losses)]
    err = [float(np.linalg.norm(prog.grad_samples[k] - ref.grad_samples[k])
                 / max(np.linalg.norm(ref.grad_samples[k]), 1e-30))
           for k in sorted(ref.grad_samples)]
    return {"loss_gap": max(loss),
            "grad_gap": max(leaf_gaps(prog.grad_norms, ref.grad_norms,
                                      sorted(ref.grad_norms)).values()),
            "update_gap": max(leaf_gaps(prog.change_norms, ref.change_norms,
                                        moved(ref)).values()),
            "grad_err": statistics.median(err)}


def batch_faults(delivered: Sequence[Dict[str, np.ndarray]],
                 expected: Sequence[Dict[str, np.ndarray]]) -> Dict[str, float]:
    """Batches that differ from the benchmark's, and rows seen twice."""
    differ = sum(any(k not in d or not np.array_equal(d[k], e[k]) for k in e)
                 for d, e in zip(delivered, expected))
    rows = [r.tobytes() for b in delivered for r in b["tokens"]]
    return {"batch_mismatch": float(differ + abs(len(delivered) - len(expected))),
            "repeated_rows": float(len(rows) - len(set(rows)))}


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each number against its limit (exact checks have limit 0); a number
    that is not finite fails."""
    out: Dict[str, Dict[str, float]] = {}
    ok = True
    for k, v in numbers.items():
        lim = limits[k]
        out[k] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, out


def lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k} {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]
