"""Seed-coverage for ``profiling/flops_profiler`` (ISSUE 5 satellite):
the cost-analysis path (MFU math) and the unknown-device peak fallback
had no tests at all."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.profiling.flops_profiler.profiler import (
    CPU_PLACEHOLDER_PEAKS, PEAK_BF16_BY_KIND, FlopsProfiler,
    get_model_profile, peak_flops_per_chip)


def test_peak_flops_on_the_cpu_backend_is_the_placeholder():
    # the CPU test backend's device_kind matches no TPU entry: the helper
    # hands out the marked CPU placeholder (an accelerator would raise)
    assert peak_flops_per_chip() == CPU_PLACEHOLDER_PEAKS[0]


def test_peak_flops_kind_table_is_ordered_most_specific_first():
    kinds = [k for k, _ in PEAK_BF16_BY_KIND]
    # "v5p"/"v5e" must match before a bare "v5 lite" substring scan;
    # every entry is distinct and the peaks are positive
    assert len(set(kinds)) == len(kinds)
    assert all(p > 0 for _, p in PEAK_BF16_BY_KIND)


def test_profile_fn_cost_analysis_and_mfu_math():
    def f(a, b):
        return a @ b

    a = jnp.ones((64, 64), jnp.float32)
    prof = FlopsProfiler()
    result = prof.profile_fn(f, a, a, runs=2)
    # a 64^3 matmul is 2*64^3 = 524288 flops (XLA counts fma as 2)
    assert result["flops"] == pytest.approx(2 * 64 ** 3, rel=0.5)
    assert result["latency_s"] > 0
    # MFU consistency: mfu == achieved / (peak * device_count)
    expect_mfu = (result["achieved_flops_per_s"]
                  / (peak_flops_per_chip() * jax.device_count()))
    assert result["mfu"] == pytest.approx(expect_mfu)
    assert result["backend"] == jax.default_backend()


def test_profile_fn_reference_hook_surface():
    prof = FlopsProfiler()
    prof.profile_fn(lambda x: x * 2, jnp.ones((8,)), runs=1)
    assert prof.get_total_flops() >= 0
    assert "FLOPs" in prof.get_total_flops(as_string=True)
    assert prof.get_total_duration() > 0
    prof.end_profile()
    assert prof.profile == {}


def test_get_model_profile_standalone_fn(tmp_path):
    out = tmp_path / "profile.txt"
    flops, macs, params = get_model_profile(
        fn=lambda a: a @ a, args=(jnp.ones((16, 16)),),
        print_profile=True, as_string=False, output_file=str(out))
    assert flops > 0 and macs == flops / 2
    assert params == 16 * 16
    assert out.read_text()  # the reference-style table was written


def test_get_model_profile_as_string_form():
    flops_s, macs_s, params_s = get_model_profile(
        fn=lambda a: a @ a, args=(jnp.ones((16, 16)),),
        print_profile=False, as_string=True)
    assert "FLOPs" in flops_s and "MACs" in macs_s
