"""The plain references agree with the repo's numpy oracle, on the
configurations' programs, at small and at the paper's sizes."""
import numpy as np
import pytest

from _perfbench_helpers import DATA
from bench import spec
from bench.kernels import conv, fft


def _oracle_and_reference(config, n, seed):
    from repro.kvi.backend import get_backend
    from repro.kvi.lowering import TraceCache
    from bench.runners.kvi_serve import build_template
    tpl = build_template(config, TraceCache())
    progs = [tpl.instantiate(seed, i) for i in range(n)]
    oracle = get_backend("oracle")
    got = [oracle.run(p).outputs for p in progs]
    inputs = {name: np.stack([next(p.mem_init[m.id] for m in p.mems
                                   if m.name == name) for p in progs])
              for name in config["data_mems"]}
    return got, spec.kernel(config["kernel"]).expected(config, inputs)


@pytest.mark.parametrize("name,base,n", [
    ("tiny-conv", DATA, 4), ("tiny-fft", DATA, 4),
    ("kvi-conv32", None, 3), ("kvi-fft256", None, 2)])
def test_reference_equals_oracle(name, base, n):
    config = spec.load_config(name, base) if base else spec.load_config(name)
    got, want = _oracle_and_reference(config, n, seed=2**40 + 3)
    assert set(want) == set(got[0])
    for i in range(n):
        for k, w in want.items():
            np.testing.assert_array_equal(got[i][k], w[i])


def test_fft_reference_of_an_impulse_is_flat():
    # x = 64 at t=0: every bin is 64 (twiddles never touch the
    # impulse's path beyond a product with w^0 = 1 in Q15)
    re = np.zeros((1, 32), np.int32)
    re[0, 0] = 64
    out_re, out_im = fft.fft_dif(re, np.zeros_like(re), 15)
    assert (out_re == 64).all() and (out_im == 0).all()


def test_conv_reference_by_hand():
    img = np.arange(16, dtype=np.int32).reshape(1, 4, 4)
    filt = np.array([[0, 0, 0], [0, 2, 0], [0, 0, 1]], np.int32)
    out = conv.conv2d(img, filt, shift=1)
    # centre tap 2*x[i+1, j+1], corner tap x[i+2, j+2], then >> 1
    want = (2 * img[0, 1:3, 1:3] + img[0, 2:4, 2:4]) >> 1
    np.testing.assert_array_equal(out[0], want)


def test_references_wrap_at_32_bits():
    big = np.full((1, 3, 3), 2**30, np.int32)
    filt = np.full((3, 3), 4, np.int32)
    # 9 * 2^32 wraps to 0 in 32 bits
    assert conv.conv2d(big, filt, 0)[0, 0, 0] == 0
