"""The reduction from a profiler trace to the program's layers
(``chipbench.scopes``) and the per-round numbers that the layer readers and
``layers.py`` give, on HLO and traces made up by hand and on one recorded
on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import catalog, harness, scopes  # noqa: E402
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
    starts, reach = [0.0, 12.0], [10.0, 20.0]
    got = [scopes._enclosing(runs, starts, reach, t)
           for t in (0, 9.9, 11, 12, 25)]
    assert got == ["jit_slab", "jit_slab", "", "jit_slabfin", ""]
    # runs that overlap: the latest to start that holds the time
    runs = [("jit_slab", 0.0, 60.0), ("jit_put", 25.0, 26.0),
            ("jit_put", 30.0, 31.0)]
    starts, reach = [0.0, 25.0, 30.0], [60.0, 60.0, 60.0]
    got = [scopes._enclosing(runs, starts, reach, t)
           for t in (20, 25.5, 28, 30.5, 50, 61)]
    assert got == ["jit_slab", "jit_put", "jit_slab", "jit_put", "jit_slab",
                   ""]
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
    assert d["span_idle_ns"] == {s: 0.0 for s in scopes.SPANS} | {
        "round.dispatch": 15 + 10, "feed.wait": 10, "slab.call": 3,
        "finalize.call": 1, "round.absorb": 10, "loss.sync": 10}
    assert d["idle_by_span_ns"] == {scopes.OUTSIDE: 5 + 5 + 5,
                                    "feed.wait": 10, "slab.call": 3,
                                    "round.dispatch": 2 + 10 - 1,
                                    "finalize.call": 1, "loss.sync": 10}
    assert d["launch_leads_ns"] == {
        "jit_slab": {"counts": [1, 1], "leads_ns": [5.0]},
        "jit_slabfin": {"counts": [1, 1], "leads_ns": [5.0]}}


#: a mesh slab: the int8 reduce's Pallas call inside ``shard_map`` is named
#: after it, not after the kernel's jit
MESH_SLAB = """HloModule jit_slab, is_scheduled=true

ENTRY %main.4 (Arg_0.1: s8[1,8], Arg_1.2: f32[1]) -> f32[8] {
  %Arg_0.1 = s8[1,8]{1,0} parameter(0)
  %Arg_1.2 = f32[1]{0} parameter(1)
  %shard_map.19 = f32[8]{0} custom-call(%Arg_1.2, %Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(slab)/uplink.encode/uplink.reduce/shard_map/pallas_call"}, backend_config={"custom_call_config":{"body":"AAA="}}
  %psum.7 = f32[8]{0} all-reduce(%shard_map.19), replica_groups={{0,1,2,3}}, to_apply=%region_0.0, metadata={op_name="jit(slab)/uplink.encode/uplink.reduce/shard_map/psum"}
  ROOT %custom-call.2 = f32[8]{0} custom-call(%psum.7), custom_call_target="Sharding", metadata={op_name="jit(slab)/uplink.encode/sharding"}
}
"""


def test_pallas_calls_are_found_by_target_whatever_their_name():
    texts = [MESH_SLAB, SLABFIN]
    assert scopes.pallas_calls(texts) == {("jit_slab", "shard_map.19")}
    ops = [Op("jit_slab", "shard_map.19", 10, 14),
           Op("jit_slab", "psum.7", 14, 20),
           Op("jit_slab", "shard_map.19", 30, 34),
           Op("jit_slab", "shard_map.19", 95, 105)]   # runs past the window
    dev = "/device:TPU:1"
    lo = scopes.Loaded({f"{dev}/XLA Ops#0": ops},
                       {dev: [("jit_slab", 10, 105)]},
                       {"/host:CPU/python#0": [("bench.window", 0, 100)]},
                       {})
    for declared in (scopes.SCOPES, ("uplink.reduce",)):
        red = scopes.reduce(lo, scopes.scope_map(texts, declared),
                            scopes=declared, spans=(),
                            calls=scopes.pallas_calls(texts))
        got = red["devices"][dev]["pallas_ns"]
        assert got["uplink.reduce"] == [4.0 + 4.0 + 5.0, 3]
        assert all(v == [0.0, 0] for k, v in got.items()
                   if k != "uplink.reduce")
    # without the HLO's calls no op is a kernel's
    red = scopes.reduce(lo, scopes.scope_map(texts), spans=())
    assert red["devices"][dev]["pallas_ns"]["uplink.reduce"] == [0.0, 0]


def test_reduce_reads_nothing_without_a_device_or_a_window(smap):
    lo = _loaded()
    assert scopes.reduce(lo._replace(ops={}), smap) is None
    assert scopes.reduce(lo._replace(host={}), smap) is None


def test_layer_numbers_per_round():
    dev = {"scopes_ns": {"client.step": 4e6, "uplink.encode": 0.0,
                         "uplink.reduce": 2e6, "server.step": 0.0},
           "span_idle_ns": {"round.dispatch": 1e6}}
    got = scopes.layer_metrics([dev], 2)
    assert got == pytest.approx({
        "client.step_ms": 2.0, "uplink.encode_ms": 0.0,
        "uplink.reduce_ms": 1.0, "server.step_ms": 0.0,
        "dispatch.idle_ms": 0.5})
    # the mean over the chips, as every device reading; a scope or span
    # the reduction lacks is left out, not read as 0
    other = {"scopes_ns": {"client.step": 8e6}, "span_idle_ns": {}}
    assert scopes.layer_metrics([dev, other], 2) == \
        pytest.approx({"client.step_ms": 3.0})
    assert scopes.layer_metrics(None, 2) == {}
    # the counters per window round; a program without one: nothing
    counters = {"rounds": 5, "program": {"feed_wait_s": 0.002,
                                         "dispatch_s": None}}
    assert scopes.counter_ms(counters, "feed_wait_s") == pytest.approx(0.4)
    assert scopes.counter_ms(counters, "dispatch_s") is None
    assert scopes.counter_ms(counters, "no_such_count") is None
    # layers.py prints the same functions' numbers
    layers = catalog.load_module(ROOT / "layers.py", "chipbench_layers")
    assert layers.scopes.layer_metrics is scopes.layer_metrics


LAYER_METRICS = ("client.step_ms", "uplink.encode_ms", "uplink.reduce_ms",
                 "server.step_ms", "dispatch.idle_ms", "dispatch.host_ms",
                 "feed.wait_ms")

#: the slab of ``SLAB`` with a scope nested in the client step, as a
#: program that names its experts would write it
NESTED = SLAB.replace('"jit(slab)/vmap(client.step)/while"',
                      '"jit(slab)/vmap(client.step)/moe.experts/while"')


def reader(name):
    return catalog.load_module(ROOT / "metrics" / f"{name}.py",
                               f"chipbench_metric_{name}")


def readings(readers, texts, counters):
    """Each reader's value as the harness hands it its reduction."""
    spans = harness.declared(readers.values())["SPANS"]
    red = harness.layer_reductions(_loaded(), texts, readers.values(), spans)
    out = {}
    for name, r in readers.items():
        ctx = {"counters": counters,
               "layers": red.get(tuple(getattr(r, "SCOPES", ())))}
        out[name] = r.read(ctx)
    return out


COUNTERS = {"rounds": 5, "traced_rounds": 2,
            "program": {"dispatch_s": 0.010, "feed_wait_s": 0.002}}


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_each_layer_reader_equals_layer_metrics(name):
    r = reader(name)
    red = scopes.reduce(_loaded(), scopes.scope_map([SLAB, SLABFIN]))
    want = scopes.layer_metrics(list(red["devices"].values()), 2)
    want.update({"dispatch.host_ms": scopes.counter_ms(COUNTERS,
                                                       "dispatch_s"),
                 "feed.wait_ms": scopes.counter_ms(COUNTERS,
                                                   "feed_wait_s")})
    got = readings({name: r}, [SLAB, SLABFIN], COUNTERS)[name]
    assert got == want[name]
    assert r.read({"counters": {"rounds": 5}, "layers": None}) is None


@pytest.mark.parametrize("extra", [("client.step", "moe.experts"),
                                   ("moe.experts",)])
def test_a_scope_declared_later_moves_no_layer_reading(extra):
    # a later reader declares a scope the program nests in client.step;
    # the seven readings stay as they were, and it reads its own scope
    seven = {n: reader(n) for n in LAYER_METRICS}
    before = readings(seven, [NESTED, SLABFIN], COUNTERS)
    new = catalog.load_module(ROOT / "metrics" / "client.step_ms.py", "new")
    new.SCOPES, new.SPANS = extra, ("loss.sync",)
    new.read = lambda ctx: ctx["layers"]["devices"]["/device:TPU:0"][
        "scopes_ns"]["moe.experts"]
    after = readings(dict(seven, new=new), [NESTED, SLABFIN], COUNTERS)
    # by its own scopes the loop and what it feeds are the experts': the
    # loop's self time, the fusion in its body and the fusion after it
    assert after.pop("new") == 20.0 + 10.0 + 10.0
    assert after == before
    assert before["client.step_ms"] == pytest.approx(20.0 / 2e6)


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


#: a device plane whose ``XLA Modules`` and ``XLA Ops`` are each split over
#: two lines, with a short program that overlaps the slab
SPLIT_PLANES = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
          events { metadata_id: 1 offset_ps: 0 duration_ps: 60000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
          events { metadata_id: 3 offset_ps: 20000 duration_ps: 10000 } }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 0
          events { metadata_id: 2 offset_ps: 32000 duration_ps: 1000 } }
  lines { id: 4 name: "XLA Ops" timestamp_ns: 0
          events { metadata_id: 4 offset_ps: 32000 duration_ps: 1000 }
          events { metadata_id: 5 offset_ps: 50000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_slab(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_broadcast_in_dim(12)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8]{0} fusion(%copy.7)" } }
  event_metadata { key: 4 value { id: 4 name: "%broadcast.1 = f32[8]{0} broadcast(%c)" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.12 = f32[8]{0} fusion(%gte.9)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
          events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def test_load_reads_every_line_of_a_name_on_a_device(tmp_path):
    from jax.profiler import ProfileData
    from chipbench import trace
    path = tmp_path / "split.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        SPLIT_PLANES))
    planes = trace.load(str(path))
    assert [n for n, _, _ in planes["/device:TPU:0"]["XLA Ops"]] == [
        "%fusion.2 = f32[8]{0} fusion(%copy.7)",
        "%broadcast.1 = f32[8]{0} broadcast(%c)",
        "%fusion.12 = f32[8]{0} fusion(%gte.9)"]
    assert trace.reduce(planes)["devices"][0]["busy_ns"] == 10 + 1 + 10
    got = scopes.load(str(path))
    assert list(got.ops) == ["/device:TPU:0/XLA Ops"]
    # an op takes the latest module to start that holds it, and the slab
    # again once the short program has ended
    assert [(o.module, o.op) for o in got.ops["/device:TPU:0/XLA Ops"]] == [
        ("jit_slab", "fusion.2"), ("jit_broadcast_in_dim", "broadcast.1"),
        ("jit_slab", "fusion.12")]
    assert [m for m, _, _ in got.modules["/device:TPU:0"]] == [
        "jit_slab", "jit_broadcast_in_dim"]
    red = scopes.reduce(got, scopes.scope_map([SLAB, SLABFIN]))
    d = red["devices"]["/device:TPU:0"]
    assert d["scopes_ns"]["uplink.reduce"] == 10.0
    assert d["scopes_ns"]["uplink.encode"] == 10.0
    assert d["scopes_ns"][scopes.UNSCOPED] == 1.0
