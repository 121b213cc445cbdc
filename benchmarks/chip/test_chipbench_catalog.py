"""A configuration, a traffic mix, a cell and a per-layer metric are found
by name: a throwaway set of them in a temporary directory runs the whole
rehearsal with no edit to any file of the benchmark, and a reader gets the
kernels, scopes, spans and counters it declares."""
import json
import os
import shutil
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import catalog, harness, scopes, trace  # noqa: E402

#: readers of a kernel, a scope, a span and a trainer counter, as a later
#: configuration would add them
READERS = {
    "tiny.kernel_ns": ('KERNELS = ("tiny_kernel",)\n\n'
                       "def read(ctx):\n"
                       "    return ctx['trace']['devices'][0]['kernels']"
                       "['tiny_kernel'][0]\n"),
    "tiny.scope_ns": ('SCOPES = ("tiny.scope",)\n\n'
                      "def read(ctx):\n"
                      "    d = ctx['layers']['devices']['/device:TPU:0']\n"
                      "    return d['scopes_ns']['tiny.scope']\n"),
    "tiny.span_idle_ns": ('SPANS = ("tiny.span",)\n\n'
                          "def read(ctx):\n"
                          "    d = ctx['layers']['devices']['/device:TPU:0']\n"
                          "    return d['span_idle_ns']['tiny.span']\n"),
    "tiny.count": ('COUNTERS = ("tiny_count", "no_such_count")\n\n'
                   "def read(ctx):\n"
                   "    p = ctx['counters']['program']\n"
                   "    assert p['no_such_count'] is None\n"
                   "    return float(p['tiny_count'])\n"),
}

#: a made-up executable: fusion.1 under the scope, the kernel without one
HLO = """HloModule jit_tiny, is_scheduled=true

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(tiny)/tiny.scope/mul"}
  ROOT %tiny_kernel.3 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call"
}
"""
HOST = [("bench.window", 0.0, 100.0), ("tiny.span", 10.0, 30.0)]
DEVICE_OPS = [("jit_tiny", "fusion.1", 20.0, 40.0),
              ("jit_tiny", "tiny_kernel.3", 50.0, 60.0)]


def made_up_trace(monkeypatch):
    """The traced stretch's trace and HLO made up by hand, and a counter of
    the trainer's made up: the rest of the run is the rehearsal's own."""
    from repro.core.engine.trainer import FedAvgTrainer
    ops = [(f"%{o} = f32[8]{{0}} fusion()", s, e) for _, o, s, e in
           DEVICE_OPS]
    monkeypatch.setattr(trace, "load", lambda path: {
        "/host:CPU": {"python": HOST}, "/device:TPU:0": {"XLA Ops": ops}})
    monkeypatch.setattr(scopes, "load", lambda path: scopes.Loaded(
        {"/device:TPU:0/XLA Ops#0": [scopes.Op(*o) for o in DEVICE_OPS]},
        {}, {"/host:CPU/python#0": HOST}, {}))
    monkeypatch.setattr(harness, "hlo_texts", lambda exp: [HLO])
    monkeypatch.setattr(FedAvgTrainer, "tiny_count", property(
        lambda self: 3 * self.dispatch_count), raising=False)


def throwaway(tmp_path):
    """A configuration, mix, cell and metric of their own under
    ``tmp_path``, with no checks file; returns the benchmark's directory."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(catalog.ROOT / "configs" / "qwen1.5-0.5b.ref.py",
                bench / "configs" / "tiny-lm.ref.py")
    base = catalog.load_json(catalog.ROOT / "configs" / "qwen1.5-0.5b.json")
    cfg = dict(base, name="tiny-lm",
               model=dict(base["model"], **base["rehearse"]["model"]),
               spec=["model.arch=qwen1.5-0.5b", "model.reduced=true"],
               rehearse={})
    (bench / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps({
        "generator": {"name": "lm_tokens", "alpha": 0.1, "num_styles": 2},
        "clients": 3, "samples_per_client": 6, "clients_per_round": 2,
        "k": 2, "batch": 2, "seq": 8, "eta": 0.05, "transport": "none",
        "cohort_chunk": None, "backend": "local", "bucket_rounds": 2}))
    (bench / "metrics" / "tiny.rounds.py").write_text(
        "def read(ctx):\n    return float(ctx['counters']['rounds'])\n")
    for name, text in READERS.items():
        (bench / "metrics" / f"{name}.py").write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-lm", "source": "x",
                     "file": "bench/configs/tiny-lm.json", "reduced": [],
                     "why": "x"}],
        "workloads": [{"name": "tiny-lm.tiny", "config": "tiny-lm",
                       "traffic": "tiny-mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "client_samples_per_s", "unit": "samples/s",
                        "better": "higher", "bound": 0.03,
                        "source": "host_clock"}],
        "per_layer": [{"name": n, "unit": "x", "better": "higher",
                       "source": "program_counter", "layer": "x",
                       "moves": "client_samples_per_s"}
                      for n in ["tiny.rounds"] + list(READERS)]}))
    return bench


def test_a_new_config_mix_and_metric_need_no_edit(tmp_path, monkeypatch):
    bench = throwaway(tmp_path)
    cell = catalog.find_cell("tiny-lm.tiny", repo=tmp_path, root=bench)
    assert cell.traffic["clients"] == 3
    assert [m["name"] for m in cell.per_layer] == \
        ["tiny.rounds"] + list(READERS)
    made_up_trace(monkeypatch)
    res = harness.run_cell("tiny-lm.tiny", 2 ** 31 + 3, 0.2, True, True,
                           time.perf_counter(), repo=tmp_path, root=bench,
                           trace_dir=str(tmp_path / "trace"))
    line = harness.result_line(res, trace=True)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["tiny.rounds"] >= 2
    # the kernel's event; the scoped fusion and the kernel, which has no
    # scope of its own and takes its operand's; the span's idle part
    assert got["tiny.kernel_ns"] == 10.0
    assert got["tiny.scope_ns"] == 20.0 + 10.0
    assert got["tiny.span_idle_ns"] == 10.0
    # three per dispatch of the window, one dispatch per bucket of 2 rounds
    assert got["tiny.count"] == 3 * res["counters"]["dispatches"] > 0
    assert list(line)[-1] == "checks"
    assert not (tmp_path / "trace").exists()


def test_the_compile_cache_keys_on_hlo_metadata(monkeypatch):
    import jax
    from repro.launch import compile_cache
    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = []
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: seen.append(getattr(jax.config, flag)))
    before = getattr(jax.config, flag)
    try:
        harness.enable_cache()
    finally:
        jax.config.update(flag, before)
    assert seen == [True]


def test_a_cell_without_limits_does_not_report(tmp_path):
    bench = throwaway(tmp_path)
    with pytest.raises(harness.Fail, match="no limits"):
        harness.run_cell("tiny-lm.tiny", 2 ** 31 + 3, 0.2, False, False,
                         time.perf_counter(), repo=tmp_path, root=bench)
