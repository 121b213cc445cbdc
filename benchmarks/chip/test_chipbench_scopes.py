"""The reduction from a profiler trace to the program's layers
(``chipbench.scopes``) and the per-round numbers ``layers.py`` prints, on
HLO and traces made up by hand and on one recorded on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import catalog, scopes  # noqa: E402
from chipbench.scopes import Op  # noqa: E402

ROOT = catalog.ROOT

SLAB = """HloModule jit_slab, is_scheduled=true

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(slab)/uplink.encode/mul"}
  ROOT %add.2 = f32[8]{0} add(%mul.1, %param_0), metadata={op_name="jit(slab)/uplink.encode/uplink.reduce/add"}
}

%fused_computation.12 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %sub.1 = f32[8]{0} subtract(%param_0.1, %param_0.1), metadata={op_name="jit(slab)/uplink.encode/sub"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = f32[8]{0} get-tuple-element(%p), index=1
  %copy.7 = f32[8]{0} copy(%gte.0)
  %fusion.2 = f32[8]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.3
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.2)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %copy.1 = f32[8]{0} copy(%Arg_0.1)
  %while.1 = (s32[], f32[8]{0}) while(%copy.1), condition=%cond, body=%body, metadata={op_name="jit(slab)/vmap(client.step)/while"}
  %gte.9 = f32[8]{0} get-tuple-element(%while.1), index=1, metadata={op_name="jit(slab)/vmap(client.step)/while"}
  ROOT %fusion.12 = f32[8]{0} fusion(%gte.9), kind=kLoop, calls=%fused_computation.12, metadata={op_name="jit(slab)/uplink.reduce/add"}
}
"""

SLABFIN = """HloModule jit_slabfin, is_scheduled=true

%fused_computation.12 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.4 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(slabfin)/server.step/add"}
}

ENTRY %main.3 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  ROOT %fusion.12 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.12
}
"""


@pytest.fixture(scope="module")
def smap():
    return scopes.scope_map([SLAB, SLABFIN])


def test_scope_of_takes_the_innermost_scope_of_a_path():
    assert scopes.scope_of("jit(slab)/vmap(client.step)/while/body/dot") \
        == "client.step"
    assert scopes.scope_of("jit(f)/uplink.encode/uplink.reduce/add") \
        == "uplink.reduce"
    assert scopes.scope_of("jit(f)/transpose(jvp(server.step))/mul") \
        == "server.step"
    assert scopes.scope_of("jit(f)/client.stepper/add") is None
    assert scopes.scope_of("") is None


def test_one_instruction_name_in_two_modules_has_two_scopes(smap):
    # fusion.12 is the uplink's in the slab and the server's in finalize
    assert smap[("jit_slab", "fusion.12")] == "uplink.encode"
    assert smap[("jit_slabfin", "fusion.12")] == "server.step"


def test_a_fusion_across_scopes_takes_its_roots_scope(smap):
    # fusion.2's root is under uplink.reduce, its other op under encode
    assert smap[("jit_slab", "fusion.2")] == "uplink.reduce"
    # fusion.12 of the slab says uplink.reduce itself; its root wins
    assert smap[("jit_slab", "fusion.12")] == "uplink.encode"


def test_ops_without_metadata_take_a_users_or_their_loops_scope(smap):
    assert smap[("jit_slab", "copy.1")] == "client.step"   # feeds the loop
    assert smap[("jit_slab", "copy.7")] == "uplink.reduce"  # feeds fusion.2
    assert smap[("jit_slab", "gte.0")] == "client.step"    # in the loop
    assert ("jit_slab", "nothing.1") not in smap


def test_an_op_takes_the_module_run_that_holds_it():
    # a device op event without an hlo_module stat: the XLA Modules line
    runs = [("jit_slab", 0.0, 10.0), ("jit_slabfin", 12.0, 20.0)]
    starts = [0.0, 12.0]
    got = [scopes._enclosing(runs, starts, t) for t in (0, 9.9, 11, 12, 25)]
    assert got == ["jit_slab", "jit_slab", "", "jit_slabfin", ""]
    assert scopes.module_name("jit_slab(1234)") == "jit_slab"


def test_scope_times_are_self_times_per_module_qualified_op(smap):
    ops = [Op("jit_slab", "while.1", 10, 50), Op("jit_slab", "fusion.2", 10,
                                                  20),
           Op("jit_slab", "fusion.12", 30, 50),
           Op("jit_slabfin", "fusion.12", 60, 70),
           Op("jit_other", "fusion.12", 80, 90)]
    got = scopes.scope_times(ops, smap, {}, 0.0, 85.0)
    assert got == {"client.step": 40 - 10 - 20, "uplink.reduce": 10.0,
                   "uplink.encode": 20.0, "server.step": 10.0,
                   scopes.UNSCOPED: 5.0}


def test_a_path_on_the_event_wins_over_the_hlo(smap):
    op = Op("jit_slab", "fusion.12", 0, 1)
    assert scopes.resolve(op, smap, {}) == "uplink.encode"
    tf = {("jit_slab", "fusion.12"): "jit(slab)/server.step/add"}
    assert scopes.resolve(op, smap, tf) == "server.step"
    assert scopes.resolve(Op("jit_x", "add.1", 0, 1), smap, {}) == \
        scopes.UNSCOPED


def test_idle_goes_to_the_innermost_span_or_outside_the_program():
    host = [("bench.window", 0, 100), ("round.dispatch", 10, 40),
            ("feed.wait", 12, 18), ("slab.call", 20, 35),
            ("PjitFunction(jit_slab)", 21, 34), ("round.absorb", 60, 70)]
    idle = [(0, 15), (30, 45), (65, 80)]
    got = scopes.idle_by_span(idle, host)
    assert got == {scopes.OUTSIDE: 10 + 5 + 10, "round.dispatch": 2 + 5,
                   "feed.wait": 3, "slab.call": 5, "round.absorb": 5}
    assert sum(got.values()) == sum(b - a for a, b in idle)


def _loaded():
    dev = "/device:TPU:0"
    ops = [Op("jit_slab", "while.1", 20, 50), Op("jit_slab", "fusion.2",
                                                 20, 30),
           Op("jit_slab", "fusion.12", 50, 60),
           Op("jit_slabfin", "fusion.12", 70, 80)]
    mods = [("jit_slab", 20, 60), ("jit_slabfin", 70, 80)]
    host = [("bench.window", 0, 100), ("round.dispatch", 5, 75),
            ("feed.wait", 5, 15), ("slab.call", 15, 18),
            ("finalize.call", 65, 66), ("round.absorb", 85, 95),
            ("loss.sync", 85, 95)]
    return scopes.Loaded({f"{dev}/XLA Ops#0": ops}, {dev: mods},
                         {"/host:CPU/python#0": host}, {})


def test_reduce_splits_a_window_by_scope_and_span(smap):
    red = scopes.reduce(_loaded(), smap)
    d = red["devices"]["/device:TPU:0"]
    assert red["window_ns"] == 100.0
    assert d["busy_ns"] == 40 + 10
    assert d["scopes_ns"] == {"client.step": 20.0, "uplink.reduce": 10.0,
                              "uplink.encode": 10.0, "server.step": 10.0}
    # idle: 0-20, 60-70, 80-100; round.dispatch covers 5-20 and 60-70
    assert d["dispatch_idle_ns"] == 15 + 10
    assert d["idle_by_span_ns"] == {scopes.OUTSIDE: 5 + 5 + 5,
                                    "feed.wait": 10, "slab.call": 3,
                                    "round.dispatch": 2 + 10 - 1,
                                    "finalize.call": 1, "loss.sync": 10}
    assert d["launch_leads_ns"] == {
        "jit_slab": {"counts": [1, 1], "leads_ns": [5.0]},
        "jit_slabfin": {"counts": [1, 1], "leads_ns": [5.0]}}


def test_reduce_reads_nothing_without_a_device_or_a_window(smap):
    lo = _loaded()
    assert scopes.reduce(lo._replace(ops={}), smap) is None
    assert scopes.reduce(lo._replace(host={}), smap) is None


def test_layer_numbers_per_round():
    layers = catalog.load_module(ROOT / "layers.py", "chipbench_layers")
    dev = {"scopes_ns": {"client.step": 4e6, "uplink.reduce": 2e6},
           "dispatch_idle_ns": 1e6}
    host = {"feed_wait_s": 0.002, "dispatch_s": 0.010}
    got = layers.layer_metrics(dev, 2, 5, host)
    assert got == pytest.approx({
        "client.step_ms": 2.0, "uplink.encode_ms": 0.0,
        "uplink.reduce_ms": 1.0, "server.step_ms": 0.0,
        "dispatch.idle_ms": 0.5, "dispatch.host_ms": 2.0,
        "feed.wait_ms": 0.4})
    # no trace: the host counters alone; a program without them: nothing
    assert set(layers.layer_metrics(None, 2, 5, host)) == {
        "dispatch.host_ms", "feed.wait_ms"}
    assert layers.layer_metrics(
        None, 2, 5, {"feed_wait_s": None, "dispatch_s": None}) == {}


def test_load_keeps_the_module_of_each_op(tmp_path):
    import jax
    import jax.numpy as jnp

    def make(name):
        def f(x):
            return jnp.tanh(x) * 2.0
        f.__name__ = name
        return jax.jit(f)
    a, b = make("first"), make("second")
    x = jnp.ones((32, 32))
    a(x).block_until_ready()
    b(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        a(x).block_until_ready()
        b(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(os.path.join(d, n) for d, _, fs in os.walk(tmp_path)
                for n in fs if n.endswith(".xplane.pb"))
    got = scopes.load(path)
    mods = {o.module for line in got.ops.values() for o in line}
    assert {"jit_first", "jit_second"} <= mods
    assert scopes.window_line(got.host, "bench.window") is not None
