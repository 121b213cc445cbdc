"""The reduction from a profiler trace to device numbers, on traces made up
by hand and on one recorded on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import catalog, trace  # noqa: E402

ROOT = catalog.ROOT


def op(name, s, e, kind="fusion"):
    return (f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p)", float(s),
            float(e))


def planes(ops, host, more=None):
    p = {"/host:CPU": {"python": host}, "/device:TPU:0": {"XLA Ops": ops}}
    if more is not None:
        p["/device:TPU:1"] = {"XLA Ops": more}
    return p


HOST = [("bench.window", 0.0, 100.0), ("bench.feed_wait", 50.0, 60.0),
        ("bench.dispatch", 5.0, 12.0)]
OPS = [op("while.1", 10, 50, "while"), op("fusion.2", 10, 20),
       op("fusion.3", 30, 50), op("int8_decompress_reduce.4", 60, 70,
                                  "custom-call"),
       op("int8_decompress_reduce.5", 80, 85, "custom-call")]


def test_busy_union_counts_nested_ops_once():
    r = trace.reduce(planes(OPS, HOST))
    assert r["window_ns"] == 100.0
    assert r["devices"][0]["busy_ns"] == 40 + 10 + 5


def test_idle_share_reader_reads_busy_over_window():
    r = trace.reduce(planes(OPS, HOST))
    mod = catalog.load_module(ROOT / "metrics" / "device.idle_share.py", "m")
    assert mod.read({"trace": r}) == pytest.approx(45.0)
    assert mod.read({"trace": None}) is None


def test_kernel_time_by_name():
    r = trace.reduce(planes(OPS, HOST))
    assert r["devices"][0]["kernels"]["int8_decompress_reduce"] == (15.0, 2)


def test_self_time_of_a_loop_excludes_its_body():
    selft = trace.self_times(OPS)
    assert selft["while.1"] == pytest.approx(40 - 10 - 20)
    assert selft["fusion.2"] == 10


def test_exposed_collective_is_the_time_no_compute_runs():
    # a loop holding a blocking all-reduce, then an async pair with
    # compute between start and done: only the collectives' own time is
    # exposed, and the loop that holds one does not hide it
    ops = [op("while.1", 0, 90, "while"),
           op("all-reduce.7", 40, 60, "all-reduce"),
           op("fusion.8", 60, 70),
           op("all-reduce-start.3", 70, 71, "all-reduce-start"),
           op("fusion.9", 71, 80),
           op("all-reduce-done.3", 80, 85, "all-reduce-done")]
    r = trace.reduce(planes(ops, HOST, more=[op("all-reduce.2", 0, 10,
                                                "all-reduce")]))
    assert r["devices"][0]["exposed_collective_ns"] == 20 + 1 + 5
    assert r["devices"][1]["exposed_collective_ns"] == 10.0
    mod = catalog.load_module(
        ROOT / "metrics" / "mesh.collective_exposed_ms.py", "m2")
    ctx = {"trace": r, "counters": {"chips": 2, "traced_rounds": 5}}
    assert mod.read(ctx) == pytest.approx(18.0 / 1e6 / 5)
    ctx["counters"]["chips"] = 1
    assert mod.read(ctx) is None


def test_idle_gaps_are_labelled_by_the_host_span_over_them():
    r = trace.reduce(planes(OPS, HOST))
    gaps = dict((round(t * 1e9), n) for n, t in r["idle_gaps"])
    assert gaps[10] in ("bench.window", "bench.feed_wait", "bench.dispatch")
    labels = [n for n, _ in r["idle_gaps"]]
    assert "bench.feed_wait" in labels          # the gap 50..60
    assert "bench.dispatch" in labels           # the gap 0..10


def test_no_device_plane_reads_nothing():
    assert trace.reduce({"/host:CPU": {"python": HOST}}) is None
    assert trace.reduce(planes([], HOST)) is None


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(os.path.join(d, n) for d, _, fs in os.walk(tmp_path)
                for n in fs if n.endswith(".xplane.pb"))
    p = trace.load(path)
    assert trace.host_span(p, "bench.window") is not None
    assert trace.reduce(p) is None      # a CPU run has no TPU plane


def test_roofline_reader_needs_every_call_and_stays_under_100():
    mod = catalog.load_module(ROOT / "metrics" / "int8_reduce_roofline.py",
                              "m3")
    peaks = catalog.load_peaks("TPU v5 lite")
    sizes = [1000, 24000]
    g = {"transport": "int8", "cohort_chunk": 1, "clients_per_round": 2}
    c = {"chips": 1, "leaf_sizes": sizes, "traced_rounds": 3}
    need = sum(m + 4 * m + 4 for m in sizes) * 2 * 3
    least_ns = need / peaks["hbm_bytes_per_s"] * 1e9
    red = {"devices": {"/device:TPU:0": {"pallas_ns": {
        "uplink.reduce": [4 * least_ns, 12]}}}}
    ctx = {"layers": red, "geometry": g, "counters": c, "peaks": peaks}
    assert mod.read(ctx) == pytest.approx(25.0)
    red["devices"]["/device:TPU:0"]["pallas_ns"]["uplink.reduce"] = [
        least_ns, 11]
    assert mod.read(ctx) is None
    assert mod.read(dict(ctx, geometry=dict(g, transport="none"))) is None
    assert mod.read(dict(ctx, layers=None)) is None


def test_roofline_reader_on_a_mesh_counts_each_chip_s_own_clients():
    mod = catalog.load_module(ROOT / "metrics" / "int8_reduce_roofline.py",
                              "m4")
    peaks = catalog.load_peaks("TPU v5 lite")
    sizes = [1000, 24000]
    g = {"transport": "int8", "cohort_chunk": 4, "clients_per_round": 4}
    c = {"chips": 4, "leaf_sizes": sizes, "traced_rounds": 3}
    # one slab a round, one client on each chip
    least_ns = sum(m + 4 * m + 4 for m in sizes) * 3 \
        / peaks["hbm_bytes_per_s"] * 1e9
    red = {"devices": {f"/device:TPU:{d}": {"pallas_ns": {
        "uplink.reduce": [2 * least_ns, 6]}} for d in range(4)}}
    ctx = {"layers": red, "geometry": g, "counters": c, "peaks": peaks}
    assert mod.read(ctx) == pytest.approx(50.0)
    # a chip whose plane lacks a call reads nothing for the whole cell
    red["devices"]["/device:TPU:0"]["pallas_ns"]["uplink.reduce"] = [
        least_ns, 5]
    assert mod.read(ctx) is None
