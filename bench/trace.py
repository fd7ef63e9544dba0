"""Reduction of a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes an XSpace (``*.xplane.pb``).  On a TPU each chip is
a plane ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds one event per HLO
operation that ran, named by the operation's HLO text
(``%fusion.306 = (bf16[...]) fusion(...), kind=kLoop, ...``); a ``while``
loop's event encloses the events of its body.  Its line ``Async XLA Ops``
holds the operations in flight beside them (copies, slices, and where the
compiler made them asynchronous, collectives).  The host's plane holds the
spans that the benchmark opens with ``jax.profiler.TraceAnnotation``
(names starting with ``bench.``), on the same clock.

Everything here is plain interval arithmetic on those events: the union of
a chip's operations is its busy time, the holes in it are idle gaps, and
the part of the collectives' time that no other operation overlaps is the
exposed collective time.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
Op = Tuple[str, int, int]  # name, start ns, end ns

#: HLO operation names of the collectives (``all-reduce.3``,
#: ``all-gather-start.1``, ``reduce-scatter.2`` ...)
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: what the host was doing when no benchmark span was open: the rest of
#: ``Trainer.train``'s loop (``device_put``, the metrics' ``float()``, logs)
NO_SPAN = "trainer_loop"
#: characters of an operation's HLO text kept as its label in a breakdown
LABEL_CHARS = 96


def tpu_lines(plane: str, line: str) -> Optional[Tuple[str, str]]:
    """(device, "ops" | "async") of a line of a TPU's plane, else None."""
    if plane.startswith("/device:TPU:"):
        kind = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(line)
        if kind:
            return plane, kind
    return None


def short_name(text: str) -> str:
    """``fusion.306`` of ``%fusion.306 = (bf16[...]) fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


@dataclass
class Reduced:
    """One traced window: its bounds, each device's operations (``ops``: the
    ``XLA Ops`` line; ``leaves``: those that enclose no other; ``async_ops``:
    the ``Async XLA Ops`` line), all clipped to it and named by their HLO
    text, and the benchmark's host spans."""

    window: Interval
    ops: Dict[str, List[Op]] = field(default_factory=dict)
    leaves: Dict[str, List[Op]] = field(default_factory=dict)
    async_ops: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Op] = field(default_factory=list)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def find_xspace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str,
         device_lines: Callable[[str, str], Optional[Tuple[str, str]]] = tpu_lines
         ) -> Reduced:
    """Reduce the XSpace at ``path`` to the window of the last
    ``bench.window`` span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw: Dict[str, Dict[str, List[Op]]] = {"ops": defaultdict(list),
                                           "async": defaultdict(list)}
    spans: List[Op] = []
    for plane in data.planes:
        for line in plane.lines:
            where = device_lines(plane.name, line.name)
            for ev in line.events:
                start = int(ev.start_ns)
                op = (ev.name, start, start + int(ev.duration_ns))
                if where is not None:
                    if op[2] > op[1]:
                        raw[where[1]][where[0]].append(op)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(op)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    w = windows[-1][1:]
    ops = {d: clip(v, w) for d, v in sorted(raw["ops"].items())}
    return Reduced(w, ops, {d: leaves(v) for d, v in ops.items()},
                   {d: clip(v, w) for d, v in sorted(raw["async"].items())},
                   [s for s in clip(spans, w) if s[0] != WINDOW_SPAN])


def clip(ops: Sequence[Op], window: Interval) -> List[Op]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]


def self_times(ops: Sequence[Op]) -> List[Tuple[str, int, bool]]:
    """(name, self time, encloses nothing) of each operation of one line,
    whose events nest (a loop's event encloses its body's)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0] * len(ops)
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i in order:
        _, s, e = ops[i]
        # an event that is not wholly inside the open one is its sibling
        while stack and ops[stack[-1]][2] < e:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [(ops[i][0], ops[i][2] - ops[i][1] - child[i], leaf[i])
            for i in range(len(ops))]


def leaves(ops: Sequence[Op]) -> List[Op]:
    return [op for op, (_, _, lf) in zip(ops, self_times(ops)) if lf]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def busy_ns(ops: Sequence[Op]) -> int:
    return length(union([(s, e) for _, s, e in ops]))


def is_collective(text: str) -> bool:
    n = short_name(text).lower()
    return any(c in n for c in COLLECTIVES)


def exposed_collective_ns(leaf_ops: Sequence[Op],
                          async_ops: Sequence[Op] = ()) -> int:
    """Time in which a collective runs (an operation of its own, or one in
    flight on the async line) and no other operation does."""
    coll = union([(s, e) for n, s, e in list(leaf_ops) + list(async_ops)
                  if is_collective(n)])
    other = union([(s, e) for n, s, e in leaf_ops if not is_collective(n)])
    return length(coll) - length(intersect(coll, other))


def gaps(ops: Sequence[Op], window: Interval) -> List[Interval]:
    """The idle intervals of one device inside the window."""
    out, t = [], window[0]
    for s, e in union([(s, e) for _, s, e in ops]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def label(gap: Interval, spans: Sequence[Op]) -> str:
    """The benchmark span that covers most of ``gap``, else ``NO_SPAN``."""
    best, name = 0, NO_SPAN
    for n, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best:
            best, name = cover, n[len(SPAN_PREFIX):]
    return name


def busiest(r: Reduced) -> str:
    return max(r.ops, key=lambda d: busy_ns(r.ops[d]))


def breakdown(r: Reduced, top: int = 10) -> Dict[str, list]:
    """On the busiest device: the operations that took most time, by self
    time (a loop's own time, not its body's) summed over their runs, each
    labelled by the start of its HLO text; and the longest idle gaps, each
    labelled by what the host was doing; seconds."""
    dev = busiest(r)
    per: Dict[str, int] = defaultdict(int)
    for n, t, _ in self_times(r.ops[dev]):
        per[n.lstrip("%")[:LABEL_CHARS]] += t
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(r.ops[dev], r.window), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[label(g, r.spans), (g[1] - g[0]) / 1e9] for g in idle]}
