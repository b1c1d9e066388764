import json

import pytest

from harness.peaks import PEAKS, UnknownDevice, peaks_for


def test_v5e_row_has_its_source_and_published_peaks():
    row = peaks_for("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert row["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in row["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)


def test_every_row_names_a_source(tmp_path):
    table = json.loads(PEAKS.read_text())
    assert table and all(row.get("source") for row in table.values())
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"X": {"source": "s", "hbm_bytes_per_s": 1}}))
    assert peaks_for("X", other)["hbm_bytes_per_s"] == 1
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v5 lite", other)
