"""The §Perf optimization knobs must be EXACT (same math, different
schedule/layout): swa_block_skip, attn_repeat_kv, moe whole-batch grouping,
mixed-precision step, pure_dp rules."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_spec, reduced_model
from repro.models.layers import attention_ref, flash_attention_xla
from repro.models.moe import moe_ffn


@pytest.mark.parametrize("W,S", [(64, 512), (128, 512), (96, 384)])
def test_swa_block_skip_exact(W, S, rng):
    B, H, KV, hd = 1, 4, 2, 32
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), jnp.float32)
    base = flash_attention_xla(q, k, v, causal=True, window=W,
                               q_block=64, kv_block=64)
    skip = flash_attention_xla(q, k, v, causal=True, window=W,
                               q_block=64, kv_block=64, swa_block_skip=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(skip),
                               rtol=1e-5, atol=1e-5)


def test_repeat_kv_exact(rng):
    B, S, H, KV, hd = 2, 256, 8, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), jnp.float32)
    a = flash_attention_xla(q, k, v, causal=True, q_block=64, kv_block=64)
    b = flash_attention_xla(q, k, v, causal=True, q_block=64, kv_block=64,
                            repeat_kv=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_moe_whole_batch_group_exact(rng):
    D, E, k = 16, 4, 2
    params = {
        "router": jnp.asarray(rng.normal(0, 0.5, (D, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(0, 0.1, (E, D, 32)), jnp.float32),
        "w_up": jnp.asarray(rng.normal(0, 0.1, (E, D, 32)), jnp.float32),
        "w_down": jnp.asarray(rng.normal(0, 0.1, (E, 32, D)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(0, 1, (16, 1, D)), jnp.float32)
    y1, _ = moe_ffn(x, params, num_experts=E, top_k=k, cap_factor=8.0)
    y2, _ = moe_ffn(x, params, num_experts=E, top_k=k, cap_factor=8.0,
                    whole_batch_group=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_mixed_precision_step_close_to_f32(rng):
    """mp training must track the f32 step (bf16 grads, f32 master)."""
    from repro.configs.base import ShapeConfig
    from repro.models import model_zoo as zoo, params as params_lib, \
        steps as steps_lib
    from repro.models.sharding import make_rules
    from repro.optim.optimizer import OptimizerConfig, adamw_init

    spec = get_spec("llama3.2-1b")
    cfg = reduced_model(spec.model)
    base = spec.parallelism.replace(remat="none", fsdp=False,
                                    sequence_parallel=False)
    rules = make_rules(None, cfg, base)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=100)
    params = params_lib.initialize(zoo.param_template(cfg),
                                   jax.random.PRNGKey(0))
    seq = rng.integers(0, 100, (2, 65)).astype(np.int32)
    batch = {"tokens": jnp.asarray(seq[:, :-1]),
             "labels": jnp.asarray(seq[:, 1:])}
    outs = []
    for mp in (False, True):
        par = base.replace(mixed_precision=mp)
        step = jax.jit(steps_lib.make_train_step(cfg, rules, par, opt_cfg))
        opt = adamw_init(params, opt_cfg)
        p2, _, m = step(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        outs.append(p2)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        # same direction/scale (bf16 grads differ in low bits only)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.2, atol=2e-3)


def test_pure_dp_rules():
    from jax.sharding import AbstractMesh, PartitionSpec as PS
    from repro.models.sharding import make_rules
    spec = get_spec("llama3.2-1b")
    mesh = AbstractMesh((16, 16), ("data", "model"))
    r = make_rules(mesh, spec.model, spec.parallelism.replace(pure_dp=True))
    assert r.spec(("batch", "seq"), (256, 4096)) == PS(("data", "model"), None)
    assert r.mapping["heads"] is None and r.mapping["mlp"] is None
    assert r.mapping["embed"] == ("data", "model")   # ZeRO param sharding


def test_pure_dp_train_step_runs(rng):
    """pure_dp rules must produce a runnable train step (CPU, no mesh)."""
    from repro.configs.base import ShapeConfig
    from repro.models import model_zoo as zoo, params as params_lib, \
        steps as steps_lib
    from repro.models.sharding import make_rules
    from repro.optim.optimizer import OptimizerConfig, adamw_init
    spec = get_spec("llama3.2-1b")
    cfg = reduced_model(spec.model)
    par = spec.parallelism.replace(remat="none", pure_dp=True)
    rules = make_rules(None, cfg, par)
    opt_cfg = OptimizerConfig()
    step = jax.jit(steps_lib.make_train_step(cfg, rules, par, opt_cfg))
    params = params_lib.initialize(zoo.param_template(cfg),
                                   jax.random.PRNGKey(0))
    opt = adamw_init(params, opt_cfg)
    seq = rng.integers(0, 100, (2, 65)).astype(np.int32)
    _, _, m = step(params, opt, {"tokens": jnp.asarray(seq[:, :-1]),
                                 "labels": jnp.asarray(seq[:, 1:])})
    assert np.isfinite(float(m["loss"]))
