"""The FLOP count against a hand count, and the peak table."""
import json
import os

import pytest

from chipbench import device
from chipbench.archs import minicpm

HERE = os.path.dirname(os.path.abspath(__file__))


def test_minicpm_train_step_flops_by_hand():
    with open(os.path.join(HERE, "configs",
                           "minicpm-2b-train.vocab-half.json")) as f:
        dm = minicpm.dims(json.load(f))
    # Tied head over this chip's slice of the vocabulary: 61,440 x 2,304.
    # Each layer: q, k, v, o at 2,304 x 2,304 and three 2,304 x 5,760.
    per_layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert per_layer == 61_046_784
    n = 61_440 * 2304 + 4 * per_layer
    assert minicpm.matmul_params(dm) == n == 385_744_896
    tokens = 2 * 512
    attention = 12 * 4 * 512 * 2304
    assert minicpm.train_step_flops(dm, 2, 512) \
        == tokens * (6 * n + attention) == 2_427_998_699_520


def test_peaks_table_refuses_an_unknown_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v9 imaginary")
