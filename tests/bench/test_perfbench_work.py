"""Operations and bytes per request, and the peaks table."""
import pytest

import _perfbench_helpers  # noqa: F401
from bench import spec
from bench.peaks import peak_for
from bench.kernels.conv import conv_work
from bench.kernels.fft import fft_work
from bench.work import least_time_s


def test_conv32_hand_count():
    # 3x3 taps over 32x32 outputs: 9216 MACs, 2 ops each; the request
    # carries the 34x34 padded image and returns 32x32, 4 bytes each,
    # plus 9 taps
    ops, nbytes = conv_work(32, 3, 4)
    assert ops == 2 * 9216
    assert nbytes == (34 * 34 + 32 * 32 + 9) * 4 == 8756


def test_fft256_hand_count():
    # 8 stages of 128 butterflies = 1024, ten ops each; 256 complex in,
    # 128 complex twiddles, 256 complex out, 4 bytes a part
    ops, nbytes = fft_work(256, 4)
    assert ops == 10 * 1024
    assert nbytes == (512 + 256 + 512) * 4 == 5120
    with pytest.raises(ValueError):
        fft_work(100, 4)


def test_request_work_reads_the_configuration_files():
    for name, want in (("kvi-conv32", (18432, 8756)),
                       ("kvi-fft256", (10240, 5120))):
        config = spec.load_config(name)
        assert spec.kernel(config["kernel"]).work(config) == want


def test_least_time_takes_the_larger_bound():
    peak = peak_for("TPU v5 lite")
    ops, nbytes = conv_work(32, 3, 4)
    t = least_time_s(ops, nbytes, peak)
    assert t["bound"] == "hbm_bytes"
    assert t["seconds"] == pytest.approx(8756 / 819e9)
    t = least_time_s(393e12, 1.0, peak)
    assert t == {"seconds": 1.0, "bound": "int8_ops"}


def test_v5e_peaks_are_the_published_ones():
    p = peak_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s) == \
        (197e12, 393e12, 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peak_for(kind)
