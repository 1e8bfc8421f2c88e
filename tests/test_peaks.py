"""Peak-rate lookups are keyed by the exact ``device_kind`` JAX
reports: every supported TPU maps to its own row, the CPU that runs
interpret-mode tests maps to a named row, and an unknown device is an
error rather than a silent v5e default."""
import jax
import pytest

from repro.core import trafficmodel as tm


@pytest.mark.parametrize(
    "kind, hbm",
    [
        ("TPU v5 lite", 819e9),  # v5e
        ("TPU v5", 2765e9),  # v5p
        ("TPU v6 lite", 1640e9),  # v6e
        ("TPU v4", 1228e9),
    ],
)
def test_tpu_kinds_map_to_their_rows(kind, hbm):
    assert tm.peak_hbm_bw(kind) == hbm
    assert tm.device_peaks(kind) is tm.DEVICE_PEAKS[kind]
    # bf16 inputs double the MXU rate of the same row.
    assert tm.peak_mxu_flops(kind, 2) == 2 * tm.peak_mxu_flops(kind, 4)


def test_generations_differ():
    rates = {
        k: (tm.peak_mxu_flops(k), tm.peak_hbm_bw(k), tm.peak_vpu_flops(k))
        for k in ("TPU v5 lite", "TPU v5", "TPU v6 lite")
    }
    assert len(set(rates.values())) == 3


@pytest.mark.parametrize(
    "kind", ["TPU v7x", "TPU v5e", "v5e", "TPU v5 lite ", "gpu"]
)
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError, match="no peak rates"):
        tm.device_peaks(kind)
    with pytest.raises(KeyError):
        tm.peak_mxu_flops(kind)


def test_cpu_maps_to_named_row():
    assert jax.devices()[0].device_kind == "cpu"
    assert tm.device_peaks() is tm.DEVICE_PEAKS["cpu"]
    assert tm.peak_vpu_flops(None) == tm.peak_vpu_flops("cpu")
