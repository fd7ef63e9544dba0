"""The program's own scopes and spans in a traced window.

The program names its layers with ``jax.named_scope``: ``attention``,
``ssm`` and ``mlp`` in each layer, ``lm_loss`` around the LM head and
loss, ``grad_sync`` and ``adamw`` in the optimizer step, and inside
``grad_sync`` one scope per collective leg (``reduce_scatter``, ``psum``,
``slow_chunk``, ``all_gather``, ``all_to_all``).  A scope reaches each
compiled instruction's ``op_name`` metadata
(``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/attention/dot_general``), and the device trace names
each operation by its instruction (``trace.short_name``).  The profiler
keeps each program's HLO in the XSpace's ``/host:metadata`` plane, so
every operation of the window maps to its ``op_name``; an instruction the
compiler made without one takes a neighbour's (``module_op_names``).

An operation belongs to the innermost scope on its ``op_name`` path,
wrappers such as ``transpose(...)`` unwrapped and the last component (the
primitive) left out.  Its phase: ``remat`` where the path holds
``rematted_computation`` (the forward recomputed in the backward pass),
``backward`` where it holds ``transpose(``, else ``forward``.

The program's training loop opens its own host spans on the trace's
clock: ``train`` per step (a step annotation), and inside it
``train.batch``, ``train.device_put``, ``train.dispatch``, ``train.fetch``
and ``train.checkpoint``.

The set of scopes is open: a reader under ``bench/metrics/`` that reads a
scope of its own declares it at module level, ``SCOPES = ("moe",)``, a
literal tuple of names.  ``known_scopes`` is the base tuple (``SCOPES``
here), then every scope a reader declares, readers in the order of their
file names; ``scope_of``, ``scope_ms`` and the ``scope_split`` line all use
it.  Innermost wins, so ``mlp/moe/...`` is ``moe`` only where a reader
declares ``moe``, and ``mlp`` otherwise.  A name that JAX itself puts on
a path (``JAX_COMPONENTS``) cannot be declared: it would take every
operation beneath it.

``bench/trace.py`` keeps neither, so the readers here read the run's
XSpace again: the newest under ``.bench_out/trace``, taken only if its
window is the reading's.  A program without the scopes or spans gives
nothing to read, and the readers then return None.

    python3 bench/scopes.py <trace dir or .xplane.pb> [steps]

prints the ``scope_split`` line of a traced benchmark window
(``.bench_out/trace/<cell>``).
"""
from __future__ import annotations

import ast
import functools
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACES = ROOT / ".bench_out" / "trace"
#: the readers, each of which may declare the scopes it reads
METRICS = ROOT / "bench" / "metrics"

#: the base scopes the program opens
SCOPES = ("attention", "ssm", "mlp", "lm_loss", "grad_sync", "adamw")
#: path components JAX puts on an op_name of its own accord
JAX_COMPONENTS = frozenset({
    "jit", "pjit", "jvp", "transpose", "vmap", "pmap", "while", "body", "cond",
    "branch", "scan", "checkpoint", "remat", "rematted_computation",
    "closed_call", "core_call", "named_call", "shard_map", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "pallas_call"})
LEGS = ("reduce_scatter", "psum", "slow_chunk", "all_gather", "all_to_all")
PHASES = ("forward", "remat", "backward")
#: where the profiler keeps each program's HLO
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
PROGRAM_STEP = "train"
PROGRAM_PREFIX = "train."


# ---------------------------------------------------------------------------
# op_name -> scope, leg, phase
# ---------------------------------------------------------------------------


def _innermost(op_name: str, known: Sequence[str]) -> Optional[str]:
    head, _, last = op_name.rpartition("/")
    # the last component is the primitive (``psum``, ``all_gather`` share
    # the legs' names), unless it is a wrapper: ``transpose(jvp(lm_loss))``
    path = op_name if "(" in last else head
    found = [t for t in re.split(r"[/()]+", path) if t in known]
    return found[-1] if found else None


def declared(path: Path) -> Tuple[str, ...]:
    """The scopes the reader at ``path`` declares (its module-level
    ``SCOPES``); a name JAX puts on paths itself, or one that is no
    identifier, is refused."""
    for node in ast.parse(path.read_text(), str(path)).body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "SCOPES" for t in node.targets):
            names = tuple(ast.literal_eval(node.value))
            for n in names:
                if not (isinstance(n, str) and n.isidentifier()):
                    raise ValueError(f"{path}: scope {n!r} is not a name")
                if n in JAX_COMPONENTS:
                    raise ValueError(f"{path}: scope {n!r} is a component JAX "
                                     f"puts on op_name paths itself")
            return names
    return ()


@functools.lru_cache(maxsize=None)
def _scopes_under(metrics: Path) -> Tuple[str, ...]:
    out = list(SCOPES)
    for path in sorted(metrics.glob("*.py")):
        out += [n for n in declared(path) if n not in out]
    return tuple(out)


def known_scopes() -> Tuple[str, ...]:
    """The base scopes, then each scope a reader under ``METRICS``
    declares, once; read once per directory."""
    return _scopes_under(METRICS)


def scope_of(op_name: str) -> Optional[str]:
    return _innermost(op_name, known_scopes())


def leg_of(op_name: str) -> Optional[str]:
    return _innermost(op_name, LEGS)


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    return "backward" if "transpose(" in op_name else "forward"


# ---------------------------------------------------------------------------
# op_names from the XSpace (protobuf wire format, no generated code)
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, the bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield num, v


def _map_value(entry: bytes) -> object:
    return next((v for k, v in _fields(entry) if k == 2), b"")


def _field(msg: bytes, num: int, default=b""):
    return next((v for k, v in _fields(msg) if k == num), default)


def _ints(v) -> List[int]:
    """A repeated integer field's values, packed or not."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def module_op_names(module: bytes) -> Dict[str, str]:
    """``{instruction name: op_name}`` of one ``HloModuleProto``.  An
    instruction that the compiler made without an op_name (a collective it
    decomposed, a loop it built around one, a copy) takes the op_name of a
    neighbour that has a scope: an operand, else a user, else the
    instruction that calls its computation, repeated until nothing
    changes.  Instructions with an op_name keep their own.

    Wire fields: HloModuleProto computations 3; HloComputationProto
    instructions 2, id 5; HloInstructionProto name 1, metadata 7, id 35,
    operand_ids 36, called_computation_ids 38; OpMetadata op_name 2."""
    names, own, links = {}, {}, defaultdict(list)
    caller: Dict[int, Tuple[int, int]] = {}
    comp_of: Dict[Tuple[int, int], int] = {}
    for c, (ck, comp) in enumerate(_fields(module)):
        if ck != 3:
            continue
        comp_id = _field(comp, 5, 0)
        for ik, ins in _fields(comp):
            if ik != 2:
                continue
            f = defaultdict(list)
            for k, v in _fields(ins):
                f[k].append(v)
            key = (c, f[35][0] if f[35] else -1)
            names[key] = f[1][0].decode() if f[1] else ""
            own[key] = _field(f[7][0], 2).decode() if f[7] else ""
            comp_of[key] = comp_id
            for o in (x for v in f[36] for x in _ints(v)):
                links[key].append((c, o))       # operands first ...
                links[(c, o)].append(key)       # ... users after
            for callee in (x for v in f[38] for x in _ints(v)):
                caller.setdefault(callee, key)
    got = {k: v for k, v in own.items() if v}
    changed = True
    while changed:
        changed = False
        for k in names:
            if k in got:
                continue
            near = links[k] + ([caller[comp_of[k]]] if comp_of[k] in caller else [])
            src = next((got[n] for n in near if n in got and scope_of(got[n])), None)
            if src is not None:
                got[k], changed = src, True
    return {names[k]: v for k, v in got.items()}


def hlo_op_names(path: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every module the XSpace at
    ``path`` holds (the profiler keeps each program's ``HloProto`` in the
    ``/host:metadata`` plane; ``module_op_names``); where two modules use
    one name, the larger module's entry stands.

    Wire fields: XSpace planes 1; XPlane name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata stats 5; XStatMetadata id 1, name 2;
    XStat metadata_id 1, str_value 5, bytes_value 6; HloProto hlo_module
    1."""
    modules: List[Dict[str, str]] = []
    for num, plane in _fields(Path(path).read_bytes()):
        if num != 1 or _field(plane, 2) != METADATA_PLANE.encode():
            continue
        stat_ids = {_field(_map_value(v), 1, 0) for k, v in _fields(plane)
                    if k == 5 and _field(_map_value(v), 2) == HLO_PROTO_STAT.encode()}
        for k, v in _fields(plane):
            if k != 4:
                continue
            for sk, st in _fields(_map_value(v)):
                if sk == 5 and _field(st, 1, 0) in stat_ids:
                    proto = _field(st, 6) or _field(st, 5)
                    modules.append(module_op_names(_field(proto, 1)))
    out: Dict[str, str] = {}
    for names in sorted(modules, key=len):
        out.update(names)
    return out


# ---------------------------------------------------------------------------
# What a traced run's XSpace holds of the program
# ---------------------------------------------------------------------------


@dataclass
class Program:
    """The program's spans in the window (``train`` and ``train.*``) and
    the ``op_name`` of each instruction of the programs that ran."""

    spans: List[trace.Op] = field(default_factory=list)
    op_names: Dict[str, str] = field(default_factory=dict)

    def named(self, name: str) -> List[trace.Op]:
        return [op for op in self.spans if op[0] == name]


def program_of(path: str) -> Tuple[Optional[trace.Interval], Program]:
    """The window of the XSpace at ``path`` (its last ``bench.window``
    span) and the program's part of it."""
    from jax.profiler import ProfileData

    spans, windows = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                op = (ev.name, start, start + int(ev.duration_ns))
                if ev.name == PROGRAM_STEP or ev.name.startswith(PROGRAM_PREFIX):
                    spans.append(op)
                elif ev.name == trace.WINDOW_SPAN:
                    windows.append(op[1:])
    if not windows:
        return None, Program()
    return windows[-1], Program(trace.clip(spans, windows[-1]),
                               hlo_op_names(path))


_CACHE: Dict[trace.Interval, Optional[Program]] = {}


def newest_xspace(root: Path) -> Optional[str]:
    found = sorted(root.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return str(found[-1]) if found else None


def program(r, root: Optional[Path] = None) -> Optional[Program]:
    """The program's part of the reading's traced window, read once per
    window from the newest XSpace under ``root`` whose window it is; the
    first read prints the ``scope_split`` line on stderr."""
    t = r.trace
    if t.window not in _CACHE:
        path = newest_xspace(root or TRACES)
        window, got = program_of(path) if path is not None else (None, None)
        if window != t.window:
            got = None
        elif t.ops and got.op_names:
            print(split_line(t, got, r.steps), file=sys.stderr)
        _CACHE[t.window] = got
    return _CACHE[t.window]


# ---------------------------------------------------------------------------
# Time by scope
# ---------------------------------------------------------------------------


def split_ns(ops: Sequence[trace.Op], op_names: Dict[str, str],
             key: Callable[[str], Optional[str]]) -> Dict[str, int]:
    """Per key, the length of the union of the operations ``key`` gives it
    by their ``op_name``; operations it gives none, or without an
    ``op_name``, are left out."""
    per: Dict[str, List[trace.Interval]] = defaultdict(list)
    for n, s, e in ops:
        op_name = op_names.get(trace.short_name(n))
        k = key(op_name) if op_name is not None else None
        if k is not None:
            per[k].append((s, e))
    return {k: trace.length(trace.union(v)) for k, v in per.items()}


def scope_ms(r, scope: str, with_async: bool = False) -> Optional[float]:
    """ms per step of the operations scoped ``scope`` on the busiest chip:
    its leaf operations, and with ``with_async`` the collectives in flight
    on its async line (not the copies in flight there, which wait beside
    other work).  None where no operation carries the scope; a scope that
    is neither a base scope nor declared by a reader is an error."""
    if scope not in known_scopes():
        raise ValueError(f"scope {scope!r} is not known: declare it in its "
                         f"reader, SCOPES = ({scope!r},)")
    t = r.trace
    if not r.steps or not t.ops:
        return None
    prog = program(r)
    if prog is None:
        return None
    dev = trace.busiest(t)
    ops = t.leaves.get(dev, []) + (_async_collectives(t, dev) if with_async else [])
    ns = split_ns(ops, prog.op_names, scope_of).get(scope)
    return None if ns is None else ns / r.steps / 1e6


def _async_collectives(t: trace.Reduced, dev: str) -> List[trace.Op]:
    return [op for op in t.async_ops.get(dev, []) if trace.is_collective(op[0])]


def split_line(t: trace.Reduced, prog: Program, steps: int) -> str:
    """One line: per scope its forward, remat and backward ms per step
    (leaf operations, busiest chip); the share of busy time that no scope
    holds (``unscoped_share``); the share of leaf time with an ``op_name``,
    its own or a neighbour's (``named_share``); per collective leg its ms per step (leaf operations
    and collectives in flight)."""
    dev = trace.busiest(t)
    leaves = t.leaves.get(dev, [])
    ms = 1e-6 / max(steps, 1)
    names = prog.op_names
    phased = split_ns(leaves, names,
                      lambda o: scope_of(o) and f"{scope_of(o)}.{phase_of(o)}")
    scoped = sum(split_ns(leaves, names, scope_of).values())
    busy = trace.busy_ns(t.ops[dev])
    leaf_ns = trace.busy_ns(leaves)
    named = trace.busy_ns([op for op in leaves if trace.short_name(op[0]) in names])
    legs = split_ns(leaves + _async_collectives(t, dev), names, leg_of)
    parts = [f"{k} {phased.get(k, 0) * ms!r}"
             for k in (f"{sc}.{ph}" for sc in known_scopes() for ph in PHASES)]
    parts.append(f"unscoped_share {1 - scoped / busy if busy else 0.0!r}")
    parts.append(f"named_share {named / leaf_ns if leaf_ns else 0.0!r}")
    parts += [f"leg.{g} {legs.get(g, 0) * ms!r}" for g in LEGS]
    return "scope_split " + " ".join(parts)


def main(argv: List[str]) -> int:
    path = argv[0] if argv[0].endswith(".xplane.pb") else trace.find_xspace(argv[0])
    t = trace.read(path)
    _, prog = program_of(path)
    steps = int(argv[1]) if len(argv) > 1 else len(prog.named(PROGRAM_STEP)) or 1
    print(split_line(t, prog, steps))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
