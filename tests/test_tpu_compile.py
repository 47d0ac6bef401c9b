"""Compiles of the served path for a described TPU v5e (no chip needed).

Interpret-mode tests cannot see what Mosaic refuses: unaligned tiles,
sub-word arithmetic the vector unit lacks, scalar stores to VMEM. These
tests compile the KVI fused-region kernel, the reduction kernels, whole
batched walks and the ``llama3.2-1b`` decode step for one v5e chip, so
a kernel change that the chip's compiler would refuse fails here.
Nothing runs; a compile that passes is not a chip run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.kdotp as kdotp_mod
import repro.kvi.pallas_backend as pb

# one slot program touching every element-wise op, with scalars that
# leave the sub-word range (so the int32 widening and re-wrap compile)
PROGRAM = (("kaddv", 2, 0, 1, 0), ("kvmul", 3, 2, 1, 0),
           ("ksvmulsc", 3, 3, None, 300), ("ksrav", 3, 3, None, 2),
           ("ksrlv", 4, 3, None, 1), ("krelu", 3, 3, None, 0),
           ("kvslt", 5, 3, 4, 0), ("ksvslt", 6, 0, None, 5),
           ("ksubv", 2, 5, 6, 0), ("ksvaddsc", 2, 2, None, -7),
           ("kvcp", 7, 2, None, 0))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels to their TPU branch: this process's JAX platform
    is the CPU, but the compile targets the described chip."""
    for mod in (pb, kdotp_mod):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("N", [1, 8])
@pytest.mark.parametrize("n", [64, 256, 1024, 4096])
@pytest.mark.parametrize("dtype", ["int8", "int16", "int32"])
def test_fused_region_compiles(one_chip, mosaic, dtype, n, N):
    dt = jnp.dtype(dtype)
    call = pb._make_fused_caller(PROGRAM, (0, 1), (2, 3, 7), 8, N, n,
                                 pb.pick_block(n, 1024), dt)
    x = jax.ShapeDtypeStruct((N, n), dt, sharding=one_chip)
    _compile(call, x, x)


@pytest.mark.parametrize("n", [64, 256, 4096])
@pytest.mark.parametrize("kernel", ["kdotp", "kvred_batched"])
def test_reduction_compiles(one_chip, mosaic, kernel, n):
    if kernel == "kdotp":
        x = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
        _compile(kdotp_mod.kdotp, x, x)
    else:
        # the backend's form: one launch reduces every row of a batch
        x = jax.ShapeDtypeStruct((8, n), jnp.int16, sharding=one_chip)
        _compile(kdotp_mod.reduce_rows, x)


@pytest.mark.parametrize("kernel", ["conv32", "fft32"])
def test_served_walk_compiles(one_chip, mosaic, kernel):
    """The whole batched walk, every region and register-file update,
    compiles as one program for the chip."""
    import numpy as np
    from repro.kvi.passes import PassPipeline
    from repro.kvi.programs import conv2d_program, fft_program

    if kernel == "conv32":
        prog = conv2d_program(np.zeros((32, 32), np.int32),
                              np.ones((3, 3), np.int32), shift=3)
    else:
        z = np.zeros(32, np.int32)
        prog = fft_program(z, z)
    prog = PassPipeline.from_spec(None).run(prog)
    walk, args, spec = pb.PallasBackend(passes=())._walk_fn(prog, 8)
    compiled = _compile(walk, *[jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=one_chip)
                                for a in args])
    assert compiled.as_text().count("tpu_custom_call") >= spec.fused_calls > 0


@pytest.mark.parametrize("kernel", ["conv32", "fft32", "matmul8_streamed"])
def test_served_walk_names_its_kernels(one_chip, mosaic, kernel):
    """Inside the compiled walk every region's custom call is named
    ``kvi.region.<k>`` and every reduction's ``kvi.reduce.<k>``, the
    names the device trace gives their ops."""
    import re

    import numpy as np
    from repro.kvi.obs import host
    from repro.kvi.passes import PassPipeline
    from repro.kvi.programs import (conv2d_program, fft_program,
                                    matmul_program)

    if kernel == "conv32":
        prog = conv2d_program(np.zeros((32, 32), np.int32),
                              np.ones((3, 3), np.int32), shift=3)
    elif kernel == "fft32":
        z = np.zeros(32, np.int32)
        prog = fft_program(z, z)
    else:
        prog = matmul_program(np.ones((8, 8), np.int32),
                              np.zeros((8, 8), np.int32), shift=2,
                              resident=False)
    prog = PassPipeline.from_spec(None).run(prog)
    walk, args, spec = pb.PallasBackend(passes=())._walk_fn(prog, 8)
    text = _compile(walk, *[jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=one_chip)
                            for a in args]).as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    scopes = [n.rsplit(".", 1)[0] for n in names]
    assert scopes.count(host.REGION_SCOPE) == spec.fused_calls
    assert scopes.count(host.REDUCE_SCOPE) == spec.reduce_calls
    assert len(names) == spec.fused_calls + spec.reduce_calls > 0


def test_llama_decode_step_fits_one_chip(one_chip):
    from repro.configs import get_spec
    from repro.models import model_zoo as zoo
    from repro.models import params as params_lib
    from repro.serving.engine import ServingEngine

    cfg = get_spec("llama3.2-1b").model
    engine = ServingEngine(cfg, None, slots=4, max_seq=128)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, jnp.dtype(x.dtype),
                                    sharding=one_chip)

    params = params_lib.tree_map(on_chip, zoo.param_template(cfg))
    cache = jax.tree_util.tree_map(on_chip, engine.cache)
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    compiled = engine._decode.lower(params, cache,
                                    {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < 16e9
