"""benchmark/device.py, the accepted yardstick's device rules: the table
of published peaks is exact, a kind that is not in it is an error, and
a measurement path on anything but a TPU exits non-zero naming what jax
found.  (``benchmark/tests`` is run by hand; this file is tier-1.)"""

import pytest

from benchmark import device


def test_v5e_peaks_are_the_published_ones():
    peaks = device.peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9
    assert peaks["source"]


@pytest.mark.parametrize("kind", ["TPU v9", "tpu v5 lite", "v5e", "cpu"])
def test_unknown_kind_is_an_error_not_a_default(kind):
    with pytest.raises(RuntimeError, match="benchmark.device.PEAKS"):
        device.peaks(kind)


def test_require_tpu_on_the_cpu_exits_nonzero_naming_the_platform(capsys):
    with pytest.raises(SystemExit) as e:
        device.require_tpu(1)
    assert e.value.code not in (0, None)
    err = capsys.readouterr().err
    assert "no TPU" in err and "platform 'cpu'" in err
