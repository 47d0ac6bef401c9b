"""Kernels are files of the benchmark, found by name: an unknown name is
refused, the files import nothing of the program, and the configurations'
template programs are those the runner built before kernels were files."""
import subprocess
import sys

import numpy as np
import pytest

from _perfbench_helpers import ROOT
from bench import spec


def test_unknown_kernel_is_refused_with_the_known_ones():
    with pytest.raises(KeyError, match=r"no_such_kernel.*'conv', 'fft'"):
        spec.kernel("no_such_kernel")


def test_kernel_files_import_nothing_of_the_program():
    code = (
        "import importlib, pkgutil, sys\n"
        "import bench.kernels\n"
        "for m in pkgutil.iter_modules(bench.kernels.__path__):\n"
        "    importlib.import_module('bench.kernels.' + m.name)\n"
        "print(sorted(m for m in sys.modules\n"
        "             if m == 'repro' or m.startswith('repro.')))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120,
                       env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT)})
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


def _direct(name):
    """The template program as built before kernels were files, with the
    same arguments, written out here."""
    from repro.kvi.programs import conv2d_program, fft_program
    if name == "kvi-conv32":
        filt = np.random.default_rng(0).integers(-8, 8, (3, 3)).astype(
            np.int32)
        return conv2d_program(np.zeros((32, 32), np.int32), filt, shift=4,
                              elem_bytes=4)
    z = np.zeros(256, np.int32)
    return fft_program(z, z, elem_bytes=4)


@pytest.mark.parametrize("name", ["kvi-conv32", "kvi-fft256"])
def test_kernel_program_is_the_one_built_directly(name):
    from repro.kvi.workload import structural_signature
    config = spec.load_config(name)
    got = spec.kernel(config["kernel"]).program(config,
                                                config["elem_bytes"])
    want = _direct(name)
    assert structural_signature(got) == structural_signature(want)
    assert sorted(got.mem_init) == sorted(want.mem_init)
    for k, v in want.mem_init.items():
        assert got.mem_init[k].dtype == v.dtype
        np.testing.assert_array_equal(got.mem_init[k], v)
