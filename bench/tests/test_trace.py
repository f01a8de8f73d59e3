"""bench/trace.py: busy/idle union, per-program and collective time."""

import pytest

from bench import trace


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert trace.total(trace.clip([(0, 4), (5, 7)], 1, 6)) == 4


def _raw():
    """Two devices; a host window span of [100, 200) ns."""
    ops0 = [("fusion.1", 90, 120), ("fusion.2", 110, 130),
            ("collective-permute.3", 125, 150), ("fusion.4", 170, 180),
            ("fusion.5", 195, 230)]
    mods0 = [("jit_exec_chunk(12)", 90, 150), ("jit_record(3)", 170, 180),
             ("jit_exec_chunk(12)", 195, 230)]
    ops1 = [("fusion.1", 100, 200)]
    host = [("bench.window", 100, 200), ("job", 100, 200),
            ("PjitFunction(record)", 155, 165)]
    return {"devices": {0: {"XLA Ops": ops0, "XLA Modules": mods0},
                        1: {"XLA Ops": ops1, "XLA Modules": []}},
            "host": host}


def test_reduce_busy_modules_collectives_and_gaps():
    got = trace.reduce(_raw(), chips=2)
    ns = 1e-9
    assert got["window_s"] == pytest.approx(100 * ns)
    # device 0 busy [100,150) + [170,180) + [195,200) = 65; device 1 = 100
    assert got["busy_s"] == pytest.approx((65 + 100) / 2 * ns)
    assert got["modules_s"]["exec_chunk"] == pytest.approx(55 * ns)
    assert got["modules_s"]["record"] == pytest.approx(10 * ns)
    assert got["collective_s"] == pytest.approx(25 * ns)
    # [125,130) overlaps fusion.2: 20 ns of the collective ran alone
    assert got["collective_exposed_s"] == pytest.approx(20 * ns)
    gaps = got["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "PjitFunction(record)"
    assert gaps[0][1] == pytest.approx(20 * ns)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    ops = dict(got["breakdown"]["device_ops"])
    assert ops["collective-permute.3"] == pytest.approx(25 * ns)


def test_one_chip_reads_the_first_device_only():
    got = trace.reduce(_raw(), chips=1)
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(65e-9)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce({"devices": {}, "host": []}, chips=1)


def test_load_reads_the_host_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here, on the CPU: the window's host span is found;
    there is no TPU plane, which the reduction refuses."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    raw = trace.load(path)
    assert raw["devices"] == {}
    spans = [(s, e) for n, s, e in raw["host"] if n == trace.WINDOW_SPAN]
    assert len(spans) == 1 and spans[0][1] > spans[0][0]
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce_dir(tmp_path, chips=1)


def test_self_times_take_nested_ops_out_of_their_container():
    """A ``while`` over two ops, one of them a ``cond`` over a third; an op
    that only overlaps the loop's end is not nested in it."""
    ops = [("while.1", 100, 200), ("fusion.2", 110, 130),
           ("cond.3", 140, 190), ("fusion.4", 150, 170),
           ("fusion.5", 190, 210), ("fusion.2", 205, 215)]
    own = trace.self_times(ops, 100, 212)
    ns = 1e-9
    assert own["while.1"] == pytest.approx(30 * ns)     # 100 - 20 - 50
    assert own["fusion.2"] == pytest.approx(27 * ns)    # 20 + 7, clipped
    assert own["cond.3"] == pytest.approx(30 * ns)
    assert own["fusion.4"] == pytest.approx(20 * ns)
    assert own["fusion.5"] == pytest.approx(20 * ns)


def test_collective_readers_read_the_collectives_per_step():
    from bench import harness
    ctx = {"trace": trace.reduce(_raw(), chips=2), "steps": 5}
    total = harness.load_reader("collective_us_per_step.paper")(ctx)
    exposed = harness.load_reader(
        "collective_exposed_us_per_step.paper")(ctx)
    assert total == pytest.approx(1e6 * 25e-9 / 5)
    assert exposed == pytest.approx(1e6 * 20e-9 / 5)


def test_collectives_are_found_by_their_hlo_text():
    """On the chip an op event is named by its instruction's HLO text."""
    raw = _raw()
    raw["devices"][0]["XLA Ops"] = [
        (f"%{n} = f32[784]{{0}} op()", s, e)
        for n, s, e in raw["devices"][0]["XLA Ops"]]
    got = trace.reduce(raw, chips=2)
    assert got["collective_s"] == pytest.approx(25e-9)
    assert got["collective_exposed_s"] == pytest.approx(20e-9)
