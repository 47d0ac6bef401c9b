"""Logical-axis sharding rules: divisibility guard, TLP/DLP mapping,
per-arch downgrade behavior (hymba heads, mixtral kv), cache-seq flip."""
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as PS

from repro.configs import get_spec
from repro.models.sharding import Rules, make_rules

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def rules_for(arch, mesh=MESH):
    spec = get_spec(arch)
    return make_rules(mesh, spec.model, spec.parallelism)


def test_batch_maps_to_tlp_axes():
    r = rules_for("llama3.2-1b", MESH)
    assert r.spec(("batch", "seq"), (256, 4096)) == PS("data", None)
    rp = rules_for("llama3.2-1b", MESH_POD)
    assert rp.spec(("batch", "seq"), (256, 4096)) == \
        PS(("pod", "data"), None)


def test_divisibility_guard_downgrades():
    r = rules_for("hymba-1.5b")
    # 25 heads don't divide the 16-way model axis -> replicate + record
    spec = r.spec(("layers", "embed", "heads", "head_dim"),
                  (32, 1600, 25, 64))
    assert spec[2] is None
    assert any(d[0] == "heads" for d in r.downgrades)
    # ffn still tensor-parallel
    assert r.spec(("layers", "embed", "mlp"), (32, 1600, 5504))[2] == "model"


def test_batch_of_one_replicates():
    r = rules_for("mamba2-1.3b")
    assert r.spec(("batch",), (1,))[0] is None


def test_kv_vs_cache_seq_flip():
    # deepseek kv=32 divides 16 -> heads sharded, cache_seq replicated
    rd = rules_for("deepseek-7b")
    assert rd.mapping["kv_heads"] == "model"
    assert rd.mapping["cache_seq"] is None
    # stablelm kv=8 doesn't -> flash-decode style seq sharding
    rs = rules_for("stablelm-12b")
    assert rs.mapping["kv_heads"] is None
    assert rs.mapping["cache_seq"] == "model"


def test_fsdp_and_sp_flags():
    rg = rules_for("grok-1-314b")
    assert rg.mapping["embed"] == "data"          # FSDP on
    assert rg.mapping["seq_sp"] == "model"        # SP on
    rl = rules_for("llama3.2-1b")
    assert rl.mapping["embed"] is None            # small model: no FSDP


def test_vocab_padding_divides_model_axis():
    from repro.models.model_zoo import padded_vocab
    for arch in ("mamba2-1.3b", "seamless-m4t-medium", "hymba-1.5b"):
        v = get_spec(arch).model.vocab_size
        assert padded_vocab(v) % 16 == 0
        assert padded_vocab(v) >= v


def test_no_mesh_is_noop():
    spec = get_spec("llama3.2-1b")
    r = make_rules(None, spec.model, spec.parallelism)
    assert r.sharding(("batch",), (8,)) is None
    x = __import__("jax").numpy.zeros((4, 4))
    assert r.constrain(x, "batch", None) is x
