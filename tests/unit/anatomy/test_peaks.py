"""Device peak table (ISSUE 17 satellite): v5p/v6e entries, the single
``peak_for_device`` lookup, and its consistency with the MFU helper."""

import pytest

from deepspeed_tpu.profiling.flops_profiler import (DevicePeak,
                                                    peak_flops_per_chip,
                                                    peak_for_device)
from deepspeed_tpu.profiling.flops_profiler.profiler import (
    CPU_PLACEHOLDER_PEAKS, PEAK_BF16_BY_KIND, PEAK_TABLE)


class FakeDev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


def test_v5p_and_v6e_entries_present():
    tags = [t for t, *_ in PEAK_TABLE]
    assert "v5p" in tags
    assert "v6e" in tags
    # substring match is first-match-wins: the specific tag must sort
    # before its prefix or "TPU v5p" would match "v5..." generically
    assert tags.index("v5p") < tags.index("v5e")
    assert tags.index("v6e") < tags.index("v6")


def test_peak_for_device_spec_match():
    p = peak_for_device(FakeDev("TPU v5p"))
    assert p.source == "spec"
    assert p.flops_per_s == 459e12
    assert p.hbm_bytes_per_s == 2765e9
    assert p.ici_bytes_per_s == 600e9
    p6 = peak_for_device(FakeDev("TPU v6e"))
    assert p6.flops_per_s == 918e12
    p4 = peak_for_device(FakeDev("TPU v4"))
    assert p4.flops_per_s == 275e12


def test_peak_for_device_cpu_placeholder():
    p = peak_for_device(FakeDev("mystery host", platform="cpu"))
    assert p.source == "backend_default"
    assert (p.flops_per_s, p.hbm_bytes_per_s,
            p.ici_bytes_per_s) == CPU_PLACEHOLDER_PEAKS


@pytest.mark.parametrize("platform", ["tpu", "gpu"])
def test_unknown_accelerator_kind_raises(platform):
    """An accelerator missing from the table is an error, not a default:
    a v5e peak under an unknown chip's utilization would be wrong without
    saying so."""
    with pytest.raises(ValueError, match="PEAK_TABLE"):
        peak_for_device(FakeDev("TPU v9 mystery", platform=platform))


def test_both_names_of_the_v5e_resolve():
    # the chip reports itself as "TPU v5 lite"; documents call it v5e
    assert (peak_for_device(FakeDev("TPU v5 lite")).flops_per_s
            == peak_for_device(FakeDev("TPU v5e")).flops_per_s == 197e12)


def test_peak_for_current_backend_never_raises():
    p = peak_for_device()
    assert p.flops_per_s > 0
    assert p.hbm_bytes_per_s > 0
    assert p.critical_intensity > 0
    d = p.to_dict()
    assert d["source"] in ("spec", "backend_default")
    assert "critical_intensity" in d


def test_mfu_helper_consistent_with_peak_table():
    assert peak_flops_per_chip() == peak_for_device().flops_per_s


def test_back_compat_bf16_view_matches_table():
    assert PEAK_BF16_BY_KIND == tuple(
        (tag, flops) for tag, flops, _, _ in PEAK_TABLE)


def test_device_peak_is_frozen_value():
    import dataclasses

    import pytest

    p = DevicePeak(kind="x", flops_per_s=1.0, hbm_bytes_per_s=2.0,
                   ici_bytes_per_s=3.0)
    assert p.critical_intensity == 0.5
    with pytest.raises(dataclasses.FrozenInstanceError):
        p.kind = "y"
