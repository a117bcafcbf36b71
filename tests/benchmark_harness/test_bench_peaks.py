"""The peaks table: v5e's published numbers, and no default for a kind
that is not in it."""

import pytest

from benchmark.peaks import peaks_for


def test_v5e():
    p = peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v5", "TPU v6 lite", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks_for(kind)
