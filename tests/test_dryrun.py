"""Dry-run machinery on a small fake-device mesh (subprocess so the 8-device
XLA flag never leaks into other tests)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.launch.compile import (build_cell, estimate_device_memory,
                                      estimate_hbm_traffic, lower_cell)
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((2, 4))
    arch, shape = sys.argv[1], sys.argv[2]
    cell = build_cell(arch, shape, mesh)
    lowered, _ = lower_cell(cell)
    compiled = lowered.compile()
    acct = analyze_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    out = {
        "flops": acct["dot_flops"],
        "coll": acct["collective_bytes"]["total"],
        "arg_bytes": mem.argument_size_in_bytes,
        "est": estimate_device_memory(cell)["total"],
        "traffic": estimate_hbm_traffic(cell)["total"],
        "downgrades": len(cell.rules.downgrades),
    }
    print("RESULT:" + json.dumps(out))
""")


def run_cell(arch, shape):
    env = dict(os.environ, PYTHONPATH=SRC)
    p = subprocess.run([sys.executable, "-c", SCRIPT, arch, shape],
                       capture_output=True, text=True, timeout=480, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


@pytest.mark.slow
def test_train_cell_on_8_fake_devices():
    r = run_cell("llama3.2-1b", "train_4k")
    assert r["flops"] > 1e12                 # per-device trip-aware flops
    assert r["coll"] > 1e6                   # TP all-reduces present
    assert r["est"] > 0 and r["traffic"] > 0


@pytest.mark.slow
def test_decode_cell_on_8_fake_devices():
    r = run_cell("mamba2-1.3b", "long_500k")
    assert r["flops"] > 1e8                  # one-token decode
    assert r["est"] > 0
