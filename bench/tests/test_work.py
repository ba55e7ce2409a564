"""The yardstick's counts and peaks."""
import pytest

from bench import work


def test_call_counts():
    c = work.sdtw_call(128, 512, 262144, spans=True)
    assert c["cells"] == 128 * 512 * 262144
    assert c["ops"] == 14 * c["cells"]
    assert c["bytes"] == 4 * (128 * 512 + 262144 + 3 * 128)
    assert work.sdtw_call(2, 3, 5, spans=False)["ops"] == 6 * 30


def test_roofline_and_unknown_device():
    pk = work.peaks("TPU v5 lite")
    share, bound = work.roofline(pk["vpu_int32_ops_per_s"], 1.0, 2.0,
                                 "TPU v5 lite")
    assert (share, bound) == (pytest.approx(50.0), "vpu")
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
