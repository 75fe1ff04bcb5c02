import json
import os

import pytest

from bench.lib import flops, peaks
from conftest import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet18_c100_forward_is_0p557_gmac_per_image():
    gmac = flops.cnn_forward(_cfg("resnet18_c100")) / 2e9
    assert gmac == pytest.approx(0.557, rel=0.005)


def test_resnet18_suffix_from_stage3_is_about_half_the_forward():
    cfg = _cfg("resnet18_c100")
    suffix = flops.cnn_suffix(cfg)
    assert suffix["stem"] == pytest.approx(flops.cnn_forward(cfg))
    assert suffix["g2b0"] / suffix["stem"] == pytest.approx(0.483, abs=0.01)
    assert suffix["head"] == 2.0 * 512 * 100


def test_peaks_of_v5e_and_unknown_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError):
        peaks.peaks("TPU v99")
