"""A configuration, a traffic mix, a cell and a per-layer metric are found
by name: a throwaway set of them in a temporary directory runs the whole
rehearsal with no edit to any file of the benchmark."""
import json
import os
import shutil
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import catalog, harness  # noqa: E402


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
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-lm", "source": "x",
                     "file": "bench/configs/tiny-lm.json", "reduced": [],
                     "why": "x"}],
        "workloads": [{"name": "tiny-lm.tiny", "config": "tiny-lm",
                       "traffic": "tiny-mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "client_samples_per_s", "unit": "samples/s",
                        "better": "higher", "bound": 0.03,
                        "source": "host_clock"}],
        "per_layer": [{"name": "tiny.rounds", "unit": "rounds",
                       "better": "higher", "source": "program_counter",
                       "layer": "x", "moves": "client_samples_per_s"}]}))
    return bench


def test_a_new_config_mix_and_metric_need_no_edit(tmp_path):
    bench = throwaway(tmp_path)
    cell = catalog.find_cell("tiny-lm.tiny", repo=tmp_path, root=bench)
    assert cell.traffic["clients"] == 3
    assert [m["name"] for m in cell.per_layer] == ["tiny.rounds"]
    res = harness.run_cell("tiny-lm.tiny", 2 ** 31 + 3, 0.2, True, True,
                           time.perf_counter(), repo=tmp_path, root=bench,
                           trace_dir=str(tmp_path / "trace"))
    line = harness.result_line(res, trace=True)
    assert line["correct"] is True
    assert line["metrics"]["tiny.rounds"]["value"] >= 2
    assert list(line)[-1] == "checks"
    assert not (tmp_path / "trace").exists()


def test_a_cell_without_limits_does_not_report(tmp_path):
    bench = throwaway(tmp_path)
    with pytest.raises(harness.Fail, match="no limits"):
        harness.run_cell("tiny-lm.tiny", 2 ** 31 + 3, 0.2, False, False,
                         time.perf_counter(), repo=tmp_path, root=bench)
