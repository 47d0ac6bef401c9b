"""Serving engine: continuous batching, slot reuse, latency accounting."""
import numpy as np
import pytest
import jax

from repro.configs import get_spec, reduced_model
from repro.models import model_zoo as zoo
from repro.models import params as params_lib
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def small_engine_parts():
    spec = get_spec("llama3.2-1b")
    cfg = reduced_model(spec.model)
    params = params_lib.initialize(zoo.param_template(cfg),
                                   jax.random.PRNGKey(0))
    return cfg, params


def test_drains_more_requests_than_slots(small_engine_parts, rng):
    cfg, params = small_engine_parts
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    for i in range(5):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(1, 90, 4 + i).astype(np.int32),
                           max_new_tokens=4))
    done = eng.run_until_drained(max_steps=500)
    assert len(done) == 5
    assert all(len(r.out_tokens) == 4 for r in done)
    assert all(r.first_token_at is not None and r.done_at is not None
               for r in done)


def test_slot_reuse_is_deterministic(small_engine_parts, rng):
    cfg, params = small_engine_parts
    prompt = rng.integers(1, 90, 6).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=prompt.copy(), max_new_tokens=5))
    done = eng.run_until_drained(max_steps=500)
    outs = {tuple(r.out_tokens) for r in done}
    assert len(outs) == 1, outs


def test_greedy_matches_decode_loop(small_engine_parts, rng):
    """Engine output == manual prefill+argmax-decode for a single request."""
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.models import steps as steps_lib
    from repro.models.sharding import make_rules
    from repro.configs.base import Parallelism

    cfg, params = small_engine_parts
    par = Parallelism(remat="none")
    rules = make_rules(None, cfg, par)
    prompt = rng.integers(1, 90, 7).astype(np.int32)

    eng = ServingEngine(cfg, params, slots=1, max_seq=64)
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new_tokens=4))
    got = eng.run_until_drained(max_steps=200)[0].out_tokens

    # manual: teacher-forced decode through the same decode step
    dshape = ShapeConfig("d", "decode", 64, 1)
    decode = jax.jit(steps_lib.make_decode_step(cfg, rules, par, dshape))
    cache = eng._init_cache()
    cache = jax.tree_util.tree_map(lambda x: x, cache)
    toks = list(prompt)
    out = []
    cur = None
    from repro.models import params as params_lib2
    cache = ServingEngine(cfg, params, slots=1, max_seq=64).cache
    for t in toks:
        logits, cache = decode(params, cache,
                               {"tokens": jnp.asarray([[t]], jnp.int32)})
    for _ in range(4):
        nxt = int(np.asarray(jnp.argmax(logits[:, -1], axis=-1))[0])
        out.append(nxt)
        logits, cache = decode(params, cache,
                               {"tokens": jnp.asarray([[nxt]], jnp.int32)})
    assert got == out


def test_seeded_weights_identical_across_processes():
    """The same seed gives the same weights in every process, whatever
    PYTHONHASHSEED is (str hashes are randomised per process)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    code = ("import jax, numpy as np\n"
            "from repro.configs import get_spec, reduced_model\n"
            "from repro.models import model_zoo as zoo, params\n"
            "cfg = reduced_model(get_spec('llama3.2-1b').model)\n"
            "p = params.initialize(zoo.param_template(cfg),\n"
            "                      jax.random.PRNGKey(0))\n"
            "print(sum(float(np.abs(np.asarray(x, np.float64)).sum())\n"
            "          for x in jax.tree_util.tree_leaves(p)))\n")
    src = str(Path(__file__).resolve().parents[1] / "src")
    sums = [subprocess.run([sys.executable, "-c", code], check=True,
                           capture_output=True, text=True, timeout=300,
                           env=dict(os.environ, PYTHONPATH=src,
                                    PYTHONHASHSEED=seed)).stdout
            for seed in ("1", "2")]
    assert sums[0] == sums[1], sums
