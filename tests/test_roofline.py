"""Roofline peak rates are looked up by device kind, never defaulted."""

import pytest

from repro.analysis.roofline import PEAKS, RooflineReport, device_peaks


def _report(kind: str) -> RooflineReport:
    return RooflineReport(
        arch="a", shape="s", mesh="m", n_devices=1, flops_per_device=197e12,
        bytes_per_device=819e9, adj_bytes_per_device=0.0, score_bytes_per_device=0.0,
        collective_bytes=0.0, inter_pod_bytes=0.0, model_flops=197e12,
        peak_memory_bytes=0.0, peak_state_bytes=0.0, collectives={}, device_kind=kind,
    )


def test_v5e_peaks_give_one_second_terms():
    rep = _report("TPU v5 lite")
    assert rep.t_compute == pytest.approx(1.0)
    assert rep.t_memory_hlo == pytest.approx(1.0)
    assert rep.row()["device_kind"] == "TPU v5 lite"
    assert all(p.source for p in PEAKS.values())


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peak rates"):
        device_peaks(kind)
    with pytest.raises(KeyError):
        _report(kind)
