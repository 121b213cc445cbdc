"""Operation counts from shapes, against the program's parameter count and
a count by hand; the step.mfu reader; the peaks table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import catalog  # noqa: E402

CFG = catalog.ROOT / "configs"


def ref(name):
    return catalog.load_module(CFG / f"{name}.ref.py", f"ref_{name}")


def model(name):
    return catalog.load_json(CFG / f"{name}.json")["model"]


def test_lm_count_uses_the_programs_parameter_count():
    from repro.configs import get_arch
    from repro.models import registry
    m = model("qwen1.5-0.5b")
    n = registry.param_count(get_arch("qwen1.5-0.5b"))
    assert ref("qwen1.5-0.5b").param_count(m) == n == 463987712
    # 6 per parameter of every matrix a token passes through (norm scales
    # and biases are not matrices), plus attention: 12 * L * d * S
    L, d, S = 24, 1024, 128
    vectors = 2 * L * d + d + 3 * L * d
    per_token = 6 * (n - vectors) + 12 * L * d * S
    assert ref("qwen1.5-0.5b").flops_per_sample(m, {"seq": S}) == \
        S * per_token


def test_mfu_reader():
    mod = catalog.load_module(catalog.ROOT / "metrics" / "step.mfu.py", "m")
    peaks = catalog.load_peaks("TPU v5 lite")
    ctx = {"peaks": peaks, "flops_per_sample": 1e9,
           "counters": {"samples_per_s": 19700.0, "chips": 1}}
    assert mod.read(ctx) == pytest.approx(10.0)
    assert mod.read(dict(ctx, peaks=None)) is None


def test_peaks_are_keyed_by_device_kind_with_a_source():
    table = catalog.load_json(catalog.ROOT / "peaks.json")
    assert "Google Cloud" in table["source"]
    p = catalog.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="not in peaks.json"):
        catalog.load_peaks("TPU v9 imaginary")
