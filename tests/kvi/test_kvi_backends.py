"""Cross-backend differential tests: ONE program definition, THREE
executors, one assertion (the point of the unified IR).

  * Oracle == CycleSim must be bit-identical int32 (same Mfu semantics).
  * Pallas (interpret mode on CPU) must match allclose (here: exactly,
    wrap-around int32 arithmetic is deterministic on all three).
  * CycleSim timing must satisfy the paper invariant
    sym-MIMD cycles <= het-MIMD cycles <= shared cycles.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels.common import interpret_mode
from repro.core.programs import conv2d_oracle
from repro.core.simulator import SimResult
from repro.kvi import KviProgramBuilder, get_backend
from repro.kvi.programs import (conv2d_program, conv2d_result, fft_program,
                                fft_result, matmul_program, matmul_result)

BACKENDS = ("oracle", "cyclesim", "pallas")


SRC = str(Path(__file__).resolve().parents[2] / "src")


def run_all(prog):
    return {n: get_backend(n).run(prog) for n in BACKENDS}


def assert_paper_invariant(res):
    c = res.cycles
    assert c["sym_mimd"] <= c["het_mimd"] <= c["shared"], c
    assert all(isinstance(t, SimResult) for t in res.timing.values())


class TestConv2dDifferential:
    @pytest.mark.parametrize("S,F,shift", [(8, 3, 3), (16, 3, 4), (8, 5, 4)])
    def test_three_backends_one_program(self, S, F, shift, rng):
        img = rng.integers(-128, 128, (S, S)).astype(np.int32)
        filt = rng.integers(-8, 8, (F, F)).astype(np.int32)
        prog = conv2d_program(img, filt, shift=shift)
        res = run_all(prog)
        want = conv2d_oracle(img, filt, shift)
        got = {n: conv2d_result(r) for n, r in res.items()}
        assert np.array_equal(got["oracle"], want)
        assert got["oracle"].dtype == np.int32
        # bit-identical int32: oracle == cyclesim
        assert np.array_equal(got["oracle"], got["cyclesim"])
        # pallas interpret mode
        np.testing.assert_allclose(got["pallas"], got["oracle"])
        assert_paper_invariant(res["cyclesim"])


class TestMatmulDifferential:
    @pytest.mark.slow
    def test_matmul64_resident(self, rng):
        A = rng.integers(-64, 64, (64, 64)).astype(np.int32)
        B = rng.integers(-64, 64, (64, 64)).astype(np.int32)
        prog = matmul_program(A, B, resident=True)
        res = run_all(prog)
        want = (A.astype(np.int64) @ B.astype(np.int64)).astype(np.int32)
        got = {n: matmul_result(r) for n, r in res.items()}
        assert np.array_equal(got["oracle"], want)
        assert np.array_equal(got["oracle"], got["cyclesim"])
        np.testing.assert_allclose(got["pallas"], got["oracle"])
        assert_paper_invariant(res["cyclesim"])

    def test_matmul16_resident_fast(self, rng):
        """SPM-resident path at a default-suite-friendly size (the 64x64
        version is @slow)."""
        A = rng.integers(-64, 64, (16, 16)).astype(np.int32)
        B = rng.integers(-64, 64, (16, 16)).astype(np.int32)
        prog = matmul_program(A, B, resident=True)
        res = run_all(prog)
        want = (A.astype(np.int64) @ B.astype(np.int64)).astype(np.int32)
        got = {n: matmul_result(r) for n, r in res.items()}
        assert np.array_equal(got["oracle"], want)
        assert np.array_equal(got["oracle"], got["cyclesim"])
        np.testing.assert_allclose(got["pallas"], got["oracle"])
        assert_paper_invariant(res["cyclesim"])

    def test_matmul_streamed_kdotp(self, rng):
        """Streamed path exercises the Pallas reduction kernels."""
        A = rng.integers(-64, 64, (8, 8)).astype(np.int32)
        B = rng.integers(-64, 64, (8, 8)).astype(np.int32)
        prog = matmul_program(A, B, shift=2, resident=False)
        res = run_all(prog)
        got = {n: matmul_result(r) for n, r in res.items()}
        want = ((A.astype(np.int64) @ B.astype(np.int64)) >> 2
                ).astype(np.int32)
        assert np.array_equal(got["oracle"], want)
        assert np.array_equal(got["oracle"], got["cyclesim"])
        np.testing.assert_allclose(got["pallas"], got["oracle"])


class TestFftDifferential:
    @pytest.mark.slow
    def test_fft256(self, rng):
        re = rng.integers(-2048, 2048, 256).astype(np.int32)
        im = rng.integers(-2048, 2048, 256).astype(np.int32)
        prog = fft_program(re, im)
        res = run_all(prog)
        got = {n: fft_result(r) for n, r in res.items()}
        ref = np.fft.fft(re + 1j * im)
        rel = np.abs(got["oracle"] - ref).max() / np.abs(ref).max()
        assert rel < 0.01, rel
        assert np.array_equal(got["oracle"], got["cyclesim"])
        np.testing.assert_allclose(got["pallas"], got["oracle"])
        assert_paper_invariant(res["cyclesim"])

    def test_fft32_fast(self, rng):
        re = rng.integers(-2048, 2048, 32).astype(np.int32)
        im = rng.integers(-2048, 2048, 32).astype(np.int32)
        prog = fft_program(re, im)
        res = run_all(prog)
        got = {n: fft_result(r) for n, r in res.items()}
        assert np.array_equal(got["oracle"], got["cyclesim"])
        np.testing.assert_allclose(got["pallas"], got["oracle"])


class TestSubwordSimd:
    @pytest.mark.parametrize("elem_bytes", [1, 2, 4])
    def test_elementwise_subword(self, elem_bytes, rng):
        dt = {1: np.int8, 2: np.int16, 4: np.int32}[elem_bytes]
        lo = -100 if elem_bytes == 1 else -1000
        x = rng.integers(lo, -lo, 32).astype(dt)
        y = rng.integers(lo, -lo, 32).astype(dt)
        b = KviProgramBuilder(f"sub{8 * elem_bytes}")
        hx = b.mem_in("x", x, elem_bytes)
        hy = b.mem_in("y", y, elem_bytes)
        vx = b.vreg("vx", 32, elem_bytes)
        vy = b.vreg("vy", 32, elem_bytes)
        b.kmemld(vx, hx)
        b.kmemld(vy, hy)
        b.kaddv(vx, vx, vy)
        b.ksvmulsc(vx, vx, scalar=3)
        b.krelu(vx, vx)
        ho = b.mem_out("o", 32, elem_bytes)
        b.kmemstr(ho, vx)
        prog = b.build()
        want = np.maximum(((x.astype(np.int64) + y) * 3
                           ).astype(dt), 0).astype(dt)
        for name in BACKENDS:
            out = get_backend(name).run(prog).outputs["o"]
            assert out.dtype == dt, name
            assert np.array_equal(out, want), name


# ---------------------------------------------------------------------------
# Property tests: random element-wise programs, three backends, one truth.
# ---------------------------------------------------------------------------

EW_OPS = ["kaddv", "ksubv", "kvmul", "ksvaddsc", "ksvmulsc", "ksrav",
          "krelu", "kvslt", "ksvslt", "kvcp"]

rand_op = st.tuples(st.sampled_from(EW_OPS), st.integers(0, 3),
                    st.integers(0, 3), st.integers(0, 12))


@given(st.lists(rand_op, min_size=1, max_size=12),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_random_elementwise_programs_agree(ops, seed):
    """Random straight-line element-wise programs over 4 vregs produce
    identical results on all three backends."""
    rng = np.random.default_rng(seed)
    n = 16
    b = KviProgramBuilder("random")
    regs = []
    for i in range(4):
        h = b.mem_in(f"x{i}", rng.integers(-1000, 1000, n).astype(np.int32))
        r = b.vreg(f"v{i}", n)
        b.kmemld(r, h)
        regs.append(r)
    for op, d, s, imm in ops:
        dst, src = regs[d], regs[s]
        if op in ("kaddv", "ksubv", "kvmul", "kvslt"):
            getattr(b, op)(dst, src, regs[(s + 1) % 4])
        elif op in ("krelu", "kvcp"):
            getattr(b, op)(dst, src)
        else:
            getattr(b, op)(dst, src, scalar=imm)
    outs = []
    for i, r in enumerate(regs):
        ho = b.mem_out(f"o{i}", n)
        b.kmemstr(ho, r)
        outs.append(f"o{i}")
    prog = b.build()
    res = {name: get_backend(name).run(prog) for name in BACKENDS}
    for o in outs:
        a = res["oracle"].outputs[o]
        assert np.array_equal(a, res["cyclesim"].outputs[o]), o
        assert np.array_equal(a, res["pallas"].outputs[o]), o


# ---------------------------------------------------------------------------
# Platform choice: made at call time, in one place.
# ---------------------------------------------------------------------------


class TestPlatform:
    def test_import_and_cyclesim_initialise_no_jax_backend(self):
        # the DSE's spawn workers only import the registry and run the
        # cycle model; none of them may open the chip's runtime
        code = ("import sys\n"
                "import repro.kvi\n"
                "from repro.kvi import get_backend\n"
                "get_backend('cyclesim')\n"
                "assert 'repro.kvi.pallas_backend' in sys.modules\n"
                "from jax._src import xla_bridge\n"
                "sys.exit(int(xla_bridge.backends_are_initialized()))\n")
        p = subprocess.run([sys.executable, "-c", code],
                           env=dict(os.environ, PYTHONPATH=SRC),
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]

    @pytest.mark.parametrize("platform,interpret",
                             [("tpu", False), ("cpu", True)])
    def test_interpret_mode_follows_platform(self, monkeypatch, platform,
                                             interpret):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert interpret_mode() is interpret

    def test_interpret_mode_raises_on_unknown_platform(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            interpret_mode()
