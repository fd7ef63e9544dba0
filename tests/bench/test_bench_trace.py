"""The benchmark's trace reduction (``bench/trace.py``), on a trace recorded
from a tiny jitted program on the CPU and on hand-made intervals."""
import time

import pytest

import bench_fixtures  # noqa: F401  (puts bench on the path)


def cpu_ops(plane, line):
    """On the CPU the XLA operations run on the PjRt client's threads."""
    if plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"):
        return "cpu", "ops"
    return None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    from bench import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.host_input"):
                time.sleep(0.03)
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.read(trace.find_xspace(str(out)), device_lines=cpu_ops)


def test_recorded_trace_busy_idle_and_gap_labels(recorded):
    from bench import trace

    ops = recorded.ops["cpu"]
    assert ops, "no operation of the jitted program was found"
    busy = trace.busy_ns(ops)
    assert 0 < busy < recorded.window_ns
    # three sleeps of 30 ms with nothing running: the device is idle for
    # at least that long, and the longest gaps fall in the host input span
    idle = recorded.window_ns - busy
    assert idle >= 3 * 30e6
    assert sum(e - s for s, e in trace.gaps(ops, recorded.window)) == idle
    bd = trace.breakdown(recorded)
    assert [g[0] for g in bd["idle_gaps"][:3]] == ["host_input"] * 3
    assert all(g[1] >= 0.029 for g in bd["idle_gaps"][:3])
    assert bd["device_ops"] and all(t > 0 for _, t in bd["device_ops"])


def test_union_intersect_and_exposed_collectives():
    from bench import trace

    ops = [("%fusion.1 = f32[8] fusion(%all-reduce.9)", 0, 10),
           ("%all-reduce.2 = f32[8] all-reduce(%x)", 5, 20), ("fusion.3", 8, 12),
           ("%all-gather-start.4 = (f32[8]) all-gather-start(%y)", 30, 40),
           ("copy.5", 35, 50)]
    assert trace.short_name(ops[0][0]) == "fusion.1"
    assert not trace.is_collective(ops[0][0])  # an operand's name is not the op
    assert trace.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert trace.busy_ns(ops) == 20 + 20
    # all-reduce 5-20 overlaps other ops over 5-12: 8 ns exposed;
    # all-gather 30-40 overlaps copy over 35-40: 5 ns exposed
    assert trace.exposed_collective_ns(ops) == 8 + 5
    # a collective in flight on the async line, under a fusion for half of it
    assert trace.exposed_collective_ns([("fusion.7", 60, 70)],
                                       [("all-reduce-start.8", 60, 80)]) == 10
    assert trace.gaps(ops, (0, 60)) == [(20, 30), (50, 60)]
    spans = [("bench.host_input", 18, 29), ("bench.step_call", 29, 31)]
    assert trace.label((20, 30), spans) == "host_input"
    assert trace.label((50, 60), spans) == trace.NO_SPAN
    assert trace.clip(ops, (9, 31))[0] == (ops[0][0], 9, 10)


def test_nested_loop_events():
    """A loop's event encloses its body's: busy time counts it once, and
    its self time is what its body's events leave."""
    from bench import trace

    ops = [("while.1", 0, 100), ("fusion.2", 10, 45), ("all-reduce.3", 45, 60),
           ("fusion.4", 70, 90), ("copy.5", 120, 130)]
    st = {n: (t, leaf) for n, t, leaf in trace.self_times(ops)}
    assert st["while.1"] == (100 - 35 - 15 - 20, False)
    assert st["fusion.2"] == (35, True) and st["copy.5"] == (10, True)
    assert [n for n, _, _ in trace.leaves(ops)] == ["fusion.2", "all-reduce.3",
                                                    "fusion.4", "copy.5"]
    assert trace.busy_ns(ops) == 110
    # the loop encloses the all-reduce, but only its body's leaves count
    assert trace.exposed_collective_ns(trace.leaves(ops)) == 15
    r = trace.Reduced((0, 140), {"d": ops})
    assert trace.breakdown(r)["device_ops"][:2] == [["fusion.2", 35e-9], ["while.1", 30e-9]]


def test_metric_readers_on_hand_made_trace():
    from bench import spec, trace
    from bench.cell import Reading

    ops = {"/device:TPU:0": [("fusion", 0, 60), ("all-reduce.1", 50, 70)],
           "/device:TPU:1": [("fusion", 0, 80)]}
    r = trace.Reduced((0, 100), ops, {d: trace.leaves(v) for d, v in ops.items()})
    reading = Reading(r, 2, [0.004, 0.006], {}, {}, 2, {})
    assert spec.metric_reader("step_device_ms")(reading) == pytest.approx(80 / 2 / 1e6)
    assert spec.metric_reader("device_idle_share")(reading) == pytest.approx(30.0)
    assert spec.metric_reader("sync_exposed_ms")(reading) == pytest.approx(10 / 2 / 1e6)
    assert spec.metric_reader("host_input_ms")(reading) == pytest.approx(5.0)
    # nothing to read: no collective ran, no kernel of that name ran
    quiet = Reading(trace.Reduced((0, 100), {"/device:TPU:0": [("fusion", 0, 60)]}),
                    2, [], {}, {}, 1, {})
    assert spec.metric_reader("sync_exposed_ms")(quiet) is None
    assert spec.metric_reader("flash_attention_roofline")(quiet) is None
    assert spec.metric_reader("host_input_ms")(quiet) is None


def test_a_traced_run_hands_its_readers_the_trainers_counters(tmp_path, monkeypatch):
    """A traced tiny run: the reading holds one counter dict per window
    step, and each is the very entry of the trainer's ``metrics_log`` for
    that step (no fetch of the benchmark's own), after the check steps."""
    import dataclasses

    from bench import cell as bcell
    from bench_fixtures import tiny_cell

    cell = tiny_cell(tmp_path)
    cell = dataclasses.replace(cell, per_layer=[{"name": "counted", "unit": "1"}])
    readings, setups = [], []
    monkeypatch.setattr(bcell.spec, "metric_reader", lambda name: readings.append)
    res = bcell.run(cell, 3_000_000_023, 0.3, True, time.perf_counter(),
                    tmp_path / "out", hook=setups.append)
    (r,), (s,) = readings, setups
    log, n = s.trainer.metrics_log, cell.workload["check_steps"]
    assert res["attempted"] == r.steps == len(r.counters) > 0
    assert len(log) == n + r.steps
    assert all(c is m for c, m in zip(r.counters, log[n:]))
    assert [c["step"] for c in r.counters] == list(range(n, n + r.steps))
    assert [c["loss"] for c in r.counters] == [m["loss"] for m in log[n:]]
    assert {"loss", "grad_norm"} <= set(r.counters[0])
