"""The readers of the program's scopes and spans (``bench/scopes.py`` and
the metrics that use it), on a trace recorded from a tiny scoped program
on the CPU and on hand-made intervals."""
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import bench_fixtures  # noqa: F401  (puts bench on the path)

SLEEP_S = 0.02
STEPS = 3


def cpu_ops(plane, line):
    """On the CPU the XLA operations run on the PjRt client's threads."""
    if plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"):
        return "cpu", "ops"
    return None


def _read(path):
    """The window as a TPU's line would hold it: the CPU client's own
    events (thread pool, executor, each operation's ``end:`` marker) left
    out, so that the operations are the leaves."""
    from bench import trace

    t = trace.read(path, device_lines=cpu_ops)
    ops = {"cpu": [op for op in t.ops["cpu"] if trace.short_name(op[0]) == op[0]
                   and not op[0].startswith(("end: ", "Thunk", "Threadpool", "Slinky"))]}
    return trace.Reduced(t.window, ops, {d: trace.leaves(v) for d, v in ops.items()},
                         {}, t.spans)


def _step():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("mlp"):
            z = jax.nn.relu(y @ x) * 2.0
        return z.sum()

    return jax.jit(f), jnp.ones((384, 384))


def record(root):
    """A window of three steps as ``Trainer.train`` lays them out, under
    the benchmark's window span, traced into ``root/cell``; returns (xplane
    path, compiled text)."""
    import jax

    from bench import scopes, trace

    f, x = _step()
    f(x).block_until_ready()
    out = root / "cell"
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for i in range(STEPS):
            with jax.profiler.StepTraceAnnotation(scopes.PROGRAM_STEP, step_num=i):
                with jax.profiler.TraceAnnotation("train.batch"):
                    with jax.profiler.TraceAnnotation("bench.host_input"):
                        time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    y = f(x)
                with jax.profiler.TraceAnnotation("train.fetch"):
                    y.block_until_ready()
    jax.profiler.stop_trace()
    return trace.find_xspace(str(out)), f.lower(x).compile().as_text()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(trace dir, xplane path, compiled text) of ``record``, run in a
    process of its own: the XSpace holds every program its process has
    loaded (``scopes.hlo_op_names``), and programs that earlier tests of
    this worker loaded would share instruction names with the step's."""
    root = tmp_path_factory.mktemp("traces")
    proc = subprocess.run([sys.executable, __file__, str(root)],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return (root, proc.stdout.strip().splitlines()[-1],
            (root / "compiled.txt").read_text())


def _reading(t, steps=STEPS):
    from bench.cell import Reading

    return Reading(t, steps, [SLEEP_S] * steps, {}, {}, 1, {})


def test_op_names_of_the_xspace_are_those_of_the_compiled_text(recorded):
    from bench import scopes

    _, path, text = recorded
    want = dict(re.findall(r'^\s*(?:ROOT )?%?(\S+) = .*?op_name="([^"]*)"', text,
                           flags=re.M))
    got = scopes.hlo_op_names(path)
    assert want and {k: got.get(k) for k in want} == want
    assert {scopes.scope_of(o) for o in got.values()} >= {"attention", "mlp"}


def test_recorded_scopes_split_the_busy_time(recorded):
    from bench import scopes, trace

    _, path, _ = recorded
    t = _read(path)
    window, prog = scopes.program_of(path)
    assert window == t.window
    split = scopes.split_ns(t.leaves["cpu"], prog.op_names, scopes.scope_of)
    assert split.get("attention", 0) > 0 and split.get("mlp", 0) > 0
    assert sum(split.values()) <= trace.busy_ns(t.ops["cpu"])
    line = scopes.split_line(t, prog, STEPS)
    assert line.startswith("scope_split attention.forward ")
    assert float(line.split(" attention.forward ")[1].split()[0]) > 0


def test_program_spans_are_read_and_the_benchmark_spans_kept_apart(recorded):
    from bench import scopes, trace

    _, path, _ = recorded
    t = _read(path)
    _, prog = scopes.program_of(path)
    assert len(prog.named(scopes.PROGRAM_STEP)) == STEPS
    assert len(prog.named("train.batch")) == STEPS
    # the benchmark's own spans and gap labels are what they were
    assert {n for n, _, _ in t.spans} == {"bench.host_input"}
    assert [g[0] for g in trace.breakdown(t)["idle_gaps"][:STEPS]] == ["host_input"] * STEPS


def test_readers_on_the_recorded_window(recorded, monkeypatch):
    from bench import scopes, spec, trace

    root, path, _ = recorded
    monkeypatch.setattr(scopes, "TRACES", root)
    t = _read(path)
    r = _reading(t)
    read = {m: spec.metric_reader(m)(r) for m in (
        "attn_ms", "mlp_ms", "lm_loss_ms", "optim_ms", "grad_sync_ms",
        "host_batch_ms", "host_loop_idle_ms", "host_input_ms", "step_device_ms")}
    assert read["attn_ms"] > 0 and read["mlp_ms"] > 0
    assert read["attn_ms"] + read["mlp_ms"] <= read["step_device_ms"]
    # scopes the program did not open have nothing to read
    assert read["lm_loss_ms"] is None and read["optim_ms"] is None
    assert read["grad_sync_ms"] is None
    # the program's span around the batch holds the benchmark's
    assert read["host_batch_ms"] >= 1e3 * SLEEP_S
    assert 0 <= read["host_loop_idle_ms"] < 1e3 * trace.length([t.window]) / 1e9 / STEPS


def test_another_window_gives_nothing_to_read(recorded, monkeypatch):
    from bench import scopes, spec, trace

    root, path, _ = recorded
    monkeypatch.setattr(scopes, "TRACES", root)
    t = _read(path)
    shifted = trace.Reduced((t.window[0] + 1, t.window[1]), t.ops, t.leaves)
    for m in ("attn_ms", "host_batch_ms", "host_loop_idle_ms"):
        assert spec.metric_reader(m)(_reading(shifted)) is None


def test_old_readers_see_no_program_spans(recorded):
    """The four readers the benchmark had read the same from a trace with
    the program's spans as its own reduction gives them."""
    from bench import spec, trace

    _, path, _ = recorded
    t = _read(path)
    r = _reading(t)
    assert spec.metric_reader("host_input_ms")(r) == pytest.approx(1e3 * SLEEP_S)
    assert spec.metric_reader("step_device_ms")(r) == trace.busy_ns(t.ops["cpu"]) / STEPS / 1e6
    assert spec.metric_reader("device_idle_share")(r) == pytest.approx(
        100 * (1 - trace.busy_ns(t.ops["cpu"]) / t.window_ns))
    assert spec.metric_reader("sync_exposed_ms")(r) is None


@pytest.mark.parametrize("op_name,scope,leg,phase", [
    ("jit(step)/jvp()/while/body/closed_call/attention/dot_general",
     "attention", None, "forward"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp", None, "remat"),
    ("jit(step)/transpose(jvp(lm_loss))/while/body/closed_call/checkpoint/mul",
     "lm_loss", None, "backward"),
    ("jit(f)/transpose(jvp(attention))", "attention", None, "backward"),
    ("jit(step)/shard_map/grad_sync/slow_chunk/psum", "grad_sync", "slow_chunk",
     "forward"),
    # a primitive that shares a leg's name is no leg of its own
    ("jit(step)/shard_map/grad_sync/psum", "grad_sync", None, "forward"),
    ("jit(step)/adamw/mul", "adamw", None, "forward"),
    ("jit(step)/jvp()/gather", None, None, "forward"),
    # a scope no reader declares is no scope: its operations stay in mlp
    ("jit(step)/jvp()/while/body/mlp/moe/dot_general", "mlp", None, "forward"),
])
def test_scope_leg_and_phase_of_an_op_name(op_name, scope, leg, phase):
    from bench import scopes

    assert (scopes.scope_of(op_name), scopes.leg_of(op_name),
            scopes.phase_of(op_name)) == (scope, leg, phase)


MOE_READER = '''from bench import scopes

SCOPES = ("moe",)


def read(r):
    return scopes.scope_ms(r, "moe")
'''


def _declare(root, monkeypatch, text=MOE_READER, name="moe_ms"):
    """Readers under ``root/metrics``: the repo's and one more, ``text``."""
    from bench import scopes

    shutil.copytree(scopes.METRICS, root / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "metrics" / f"{name}.py").write_text(text)
    monkeypatch.setattr(scopes, "METRICS", root / "metrics")


def test_a_scope_a_reader_declares(tmp_path, monkeypatch):
    """Hand-made: a dense fusion under ``mlp`` and a grouped matmul under
    ``mlp/moe``.  With ``moe`` declared by a reader, the grouped matmul is
    ``moe``'s in ``scope_of``, ``scope_ms`` and ``scope_split``, and ``mlp``
    keeps the rest; undeclared, ``mlp`` holds both and ``moe`` is unknown."""
    from bench import scopes, spec, trace

    dev = "/device:TPU:0"
    moe_op = ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
              "rematted_computation/mlp/moe/dot_general")
    ops = {dev: [("%fusion.1 = bf16[8] fusion()", 0, 30),
                 ("%fusion.2 = bf16[8] fusion()", 30, 80)]}
    t = trace.Reduced((0, 100), ops, {d: trace.leaves(v) for d, v in ops.items()})
    prog = scopes.Program(op_names={"fusion.1": "jit(step)/jvp()/mlp/dot_general",
                                    "fusion.2": moe_op})
    scopes._CACHE[t.window] = prog
    try:
        r = _reading(t, steps=2)
        assert spec.metric_reader("mlp_ms")(r) == pytest.approx(80 / 2 / 1e6)
        with pytest.raises(ValueError, match="declare it"):
            scopes.scope_ms(r, "moe")
        _declare(tmp_path, monkeypatch)
        assert scopes.known_scopes() == scopes.SCOPES + ("moe",)
        assert (scopes.scope_of(moe_op), scopes.phase_of(moe_op)) == ("moe", "remat")
        assert spec.metric_reader("mlp_ms")(r) == pytest.approx(30 / 2 / 1e6)
        assert spec.metric_reader("moe_ms", parts=tmp_path)(r) == pytest.approx(50 / 2 / 1e6)
        line = scopes.split_line(t, prog, 2)
        assert f" moe.remat {50 / 2 * 1e-6!r} " in line
        assert f" mlp.forward {30 / 2 * 1e-6!r} " in line
    finally:
        del scopes._CACHE[t.window]


@pytest.mark.parametrize("name,error", [
    ("jvp", "component JAX"), ("transpose", "component JAX"),
    ("while", "component JAX"), ("body", "component JAX"),
    ("checkpoint", "component JAX"), ("rematted_computation", "component JAX"),
    ("mlp/moe", "not a name"),
])
def test_a_jax_path_component_cannot_be_declared(tmp_path, monkeypatch, name, error):
    from bench import scopes

    _declare(tmp_path, monkeypatch, f"SCOPES = ({name!r},)\n", name="bad")
    with pytest.raises(ValueError, match=error):
        scopes.known_scopes()


def test_a_declared_scope_leaves_the_base_split_unchanged(recorded, tmp_path, monkeypatch):
    """On the recorded window, which opens no ``moe`` scope: declaring one
    adds its zero parts to the ``scope_split`` line and changes nothing
    else, nor any base scope's reading."""
    from bench import scopes, spec

    root, path, _ = recorded
    monkeypatch.setattr(scopes, "TRACES", root)
    t = _read(path)

    def readings():
        scopes._CACHE.clear()
        _, prog = scopes.program_of(path)
        parts = scopes.split_line(t, prog, STEPS).split()[1:]
        r = _reading(t)
        return (dict(zip(parts[::2], map(float, parts[1::2]))),
                {m: spec.metric_reader(m)(r) for m in ("attn_ms", "mlp_ms", "lm_loss_ms")})

    split, read = readings()
    _declare(tmp_path, monkeypatch)
    declared, read_declared = readings()
    scopes._CACHE.clear()
    assert read_declared == read and read["mlp_ms"] > 0
    assert {k: declared[k] for k in split} == split
    assert {k: v for k, v in declared.items() if k not in split} == {
        "moe.forward": 0.0, "moe.remat": 0.0, "moe.backward": 0.0}


def test_grad_sync_counts_its_async_time_and_legs():
    """Hand-made: a grad-sync collective in flight beside an update; the
    sync's total time counts it, the optimizer's does not, and a copy in
    flight counts for neither."""
    from bench import scopes, spec, trace

    dev = "/device:TPU:0"
    ops = {dev: [("%fusion.1 = f32[8] fusion()", 0, 40),
                 ("%all-reduce-start.2 = f32[8] all-reduce-start()", 40, 42),
                 ("%fusion.3 = f32[8] fusion()", 42, 60),
                 ("%all-reduce-done.4 = f32[8] all-reduce-done()", 60, 70)]}
    async_ops = {dev: [("%all-reduce-start.2 = f32[8] all-reduce-start()", 42, 68),
                       ("%copy-start.5 = f32[8] copy-start()", 70, 90)]}
    t = trace.Reduced((0, 100), ops, {d: trace.leaves(v) for d, v in ops.items()},
                      async_ops)
    scopes._CACHE[t.window] = scopes.Program(op_names={
        "fusion.1": "jit(step)/jvp()/attention/dot_general",
        "all-reduce-start.2": "jit(step)/shard_map/grad_sync/slow_chunk/psum",
        "fusion.3": "jit(step)/shard_map/adamw/mul",
        "all-reduce-done.4": "jit(step)/shard_map/grad_sync/slow_chunk/psum",
        "copy-start.5": "jit(step)/shard_map/grad_sync/convert_element_type"})
    try:
        r = _reading(t, steps=2)
        assert spec.metric_reader("grad_sync_ms")(r) == pytest.approx((70 - 40) / 2 / 1e6)
        assert spec.metric_reader("optim_ms")(r) == pytest.approx(18 / 2 / 1e6)
        assert spec.metric_reader("attn_ms")(r) == pytest.approx(40 / 2 / 1e6)
        legs = scopes.split_ns(t.leaves[dev] + t.async_ops[dev],
                               scopes._CACHE[t.window].op_names, scopes.leg_of)
        assert legs == {"slow_chunk": 70 - 40}
    finally:
        del scopes._CACHE[t.window]


def test_loop_idle_leaves_out_the_batch():
    from bench import scopes, spec, trace

    ops = {"/device:TPU:0": [("a", 10, 50), ("b", 70, 95)],
           "/device:TPU:1": [("a", 10, 50), ("b", 65, 95)]}
    t = trace.Reduced((0, 100), ops, {d: trace.leaves(v) for d, v in ops.items()})
    scopes._CACHE[t.window] = scopes.Program(spans=[
        ("train", 0, 50), ("train.batch", 0, 8),
        ("train", 50, 100), ("train.batch", 52, 62)])
    try:
        r = _reading(t, steps=2)
        # idle 0-10, 50-70, 95-100 on the idler chip; batch covers 0-8, 52-62
        assert spec.metric_reader("host_loop_idle_ms")(r) == pytest.approx(
            (35 - 8 - 10) / 2 / 1e6)
        assert spec.metric_reader("host_batch_ms")(r) == pytest.approx(18 / 2 / 1e6)
    finally:
        del scopes._CACHE[t.window]


def _proto(*fields):
    """Protobuf wire bytes of (field number, value) pairs: an int is a
    varint, bytes or str a length-delimited field, a list packed varints."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = b"".join(varint(x) for x in v) if isinstance(v, list) else (
                v.encode() if isinstance(v, str) else v)
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _instruction(name, iid, op_name=None, operands=(), calls=()):
    fields = [(1, name), (35, iid), (36, list(operands)), (38, list(calls))]
    if op_name is not None:
        fields.append((7, _proto((2, op_name))))
    return _proto(*fields)


def test_compiler_made_instructions_take_a_neighbours_scope():
    """An instruction without an op_name takes one from an operand, else a
    user, else the instruction that calls its computation; one with an
    op_name outside every scope keeps its own and lends nothing."""
    from bench import scopes

    sync, update = "jit(f)/grad_sync/convert", "jit(f)/adamw/mul"
    entry = _proto(
        (5, 1),
        (2, _instruction("a", 1, sync)),
        (2, _instruction("b", 2, operands=[1])),
        (2, _instruction("c", 3)),
        (2, _instruction("d", 4, update, operands=[3])),
        (2, _instruction("while", 5, operands=[2], calls=[2])),
        (2, _instruction("gather", 6, "jit(f)/gather")),
        (2, _instruction("h", 7, operands=[6])))
    body = _proto((5, 2), (2, _instruction("f", 1)))
    got = scopes.module_op_names(_proto((3, entry), (3, body)))
    assert got == {"a": sync, "b": sync, "c": update, "d": update,
                   "while": sync, "f": sync, "gather": "jit(f)/gather"}


if __name__ == "__main__":
    path, text = record(Path(sys.argv[1]))
    (Path(sys.argv[1]) / "compiled.txt").write_text(text)
    print(path)
