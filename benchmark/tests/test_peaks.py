import pytest

from benchmark.peaks import peaks


def test_the_v5e_as_google_cloud_documents_it():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(LookupError, match="TPU v9"):
        peaks("TPU v9")
    with pytest.raises(LookupError):
        peaks("cpu")
