import os
import sys

# the harness's modules import by their bare names, as bench/run.py does
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.join(os.path.dirname(_BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json

import pytest

import peaks


def test_v5e_peaks_and_source():
    p = peaks.lookup("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with open(peaks.PATH) as f:
        assert "TPU v5e" in json.load(f)["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup(kind)


def test_peaks_file_sits_beside_the_module():
    assert os.path.dirname(peaks.PATH) == os.path.dirname(peaks.__file__)
