"""Unit tests for the logical-axis sharding substrate (repro.dist.sharding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.dist import sharding as sh

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 (fake) devices")


def _mesh24():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# ---------------------------------------------------------------------------
# rules table
# ---------------------------------------------------------------------------


def test_rules_get_override_missing():
    r = sh.ShardingRules({"batch": ("data",), "tp": "model"})
    assert r.get("batch") == ("data",)
    assert r.get("nonexistent") is None
    r2 = r.override(tp=None, vocab="model")
    assert r2.get("tp") is None and r2.get("vocab") == "model"
    assert r.get("tp") == "model"          # original untouched
    assert r2 != r


def test_default_rules_multi_pod():
    r = sh.default_rules(multi_pod=True)
    assert r.table["batch"] == ("pod", "data")
    assert sh.default_rules().table["batch"] == ("data",)


# ---------------------------------------------------------------------------
# context + lookups
# ---------------------------------------------------------------------------


def test_lookups_degrade_outside_ctx():
    assert sh.active_mesh() is None
    assert sh.axis_for("batch") is None
    assert sh.axis_size_of("batch") == 1
    x = jnp.ones((4, 4))
    assert sh.constrain(x, "batch", "tp") is x
    assert sh.gather_fsdp({"wq": x})["wq"] is x


@needs8
def test_axis_lookups_in_ctx():
    mesh = _mesh24()
    rules = sh.default_rules()
    with sh.sharding_ctx(mesh, rules):
        assert sh.active_mesh() is mesh
        assert sh.axis_for("batch") == ("data",)
        assert sh.axis_for("tp") == "model"
        assert sh.axis_size_of("tp") == 4
        assert sh.axis_size_of("batch") == 2
        # mapped axis absent from this mesh -> None
        with sh.sharding_ctx(mesh, sh.default_rules(multi_pod=True)):
            assert sh.axis_for("batch") == ("data",)   # 'pod' dropped
            assert sh.axis_size_of("batch") == 2
    assert sh.active_mesh() is None


# ---------------------------------------------------------------------------
# constrain
# ---------------------------------------------------------------------------


@needs8
def test_constrain_dedupes_mesh_axes_and_checks_divisibility():
    mesh = _mesh24()
    rules = sh.default_rules()              # seq_act and tp both 'model'
    x = jnp.ones((4, 8, 12))

    def f(a):
        return sh.constrain(a, "seq_act", "tp", None)

    with sh.sharding_ctx(mesh, rules):
        lowered = jax.jit(f).lower(x).compile()
        out = jax.jit(f)(x)
    # dim0 got 'model'; the duplicate on dim1 was dropped, so this
    # compiles instead of raising "axis used twice"
    assert out.shape == x.shape
    assert lowered is not None

    y = jnp.ones((5, 3))                    # 5 % 2 != 0, 3 % 4 != 0
    with sh.sharding_ctx(mesh, rules):
        out = jax.jit(lambda a: sh.constrain(a, "batch", "tp"))(y)
    np.testing.assert_array_equal(np.asarray(out), np.ones((5, 3)))


# ---------------------------------------------------------------------------
# param partition specs + gather_fsdp
# ---------------------------------------------------------------------------


def _toy_params():
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    return {
        "embed": sds((64, 16), f32),
        "layers": {
            "ln1": sds((4, 16), f32),
            "attn": {"wq": sds((4, 16, 32), f32),
                     "wo": sds((4, 32, 16), f32)},
            "moe": {"router": sds((4, 16, 8), f32),
                    "w_up": sds((4, 8, 16, 32), f32),
                    "w_down": sds((4, 8, 32, 16), f32),
                    "shared": {"w_up": sds((4, 16, 32), f32)}},
        },
        "final_norm": sds((16,), f32),
    }


@needs8
def test_param_partition_specs_name_rules():
    mesh = _mesh24()
    rules = sh.default_rules().override(vocab="model")
    with sh.sharding_ctx(mesh, rules):
        specs = sh.param_partition_specs(_toy_params(), rules)
    # single-axis tuples are collapsed to bare names by the sanitizer
    assert specs["embed"] == P("model", "data")
    # stacked leading layer dim replicated, core dims fsdp x tp
    assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["layers"]["attn"]["wo"] == P(None, "model", "data")
    # stacked experts: expert axis on E; shared expert is a plain mlp
    assert specs["layers"]["moe"]["w_up"] == P(None, "model", None, None)
    assert specs["layers"]["moe"]["shared"]["w_up"] == \
        P(None, "data", "model")
    assert specs["layers"]["moe"]["router"] == P(None, None, None)
    assert specs["layers"]["ln1"] == P(None, None)
    assert specs["final_norm"] == P(None)


def test_param_partition_specs_requires_rules_outside_ctx():
    with pytest.raises(ValueError):
        sh.param_partition_specs(_toy_params())


@needs8
def test_param_partition_specs_divisibility_fallback():
    mesh = _mesh24()
    rules = sh.default_rules()
    sds = jax.ShapeDtypeStruct
    tree = {"wq": sds((16, 30), jnp.float32)}   # 30 % 4 != 0 -> tp dropped
    with sh.sharding_ctx(mesh, rules):
        specs = sh.param_partition_specs(tree, rules)
    assert specs["wq"] == P("data", None)


@needs8
def test_gather_fsdp_unshards_fsdp_dims():
    mesh = _mesh24()
    rules = sh.default_rules()
    wq = jnp.ones((16, 32))

    def f(p):
        return sh.gather_fsdp(p)["wq"] * 1.0

    with sh.sharding_ctx(mesh, rules):
        out = jax.jit(f)({"wq": wq})
        txt = jax.jit(f).lower({"wq": wq}).as_text()
    # the constraint inside the jit replicates the fsdp (data) dim while
    # keeping tp: sharding annotation mentions only the model axis split
    assert out.shape == (16, 32)
    assert "sharding" in txt


@needs8
@pytest.mark.parametrize("model", ["tgn", "tgat", "dysat", "graphsage",
                                   "gat"])
def test_gnn_param_partition_specs(model):
    """Every models/gnn.py parameter resolves to a PartitionSpec and
    named_shardings places the full tree on the 8-device mesh without
    replication/divisibility errors (values intact after device_put)."""
    from repro.configs.tgn_gdelt import GNN_MODELS
    from repro.models import gnn as G

    cfg = GNN_MODELS[model](d_node=8, d_edge=8, d_time=8, d_hidden=16,
                            d_memory=16, n_heads=2)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    mesh = _mesh24()
    rules = sh.default_rules()
    with sh.sharding_ctx(mesh, rules):
        specs = sh.param_partition_specs(params, rules)

    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    param_leaves = jax.tree_util.tree_leaves(params)
    assert len(spec_leaves) == len(param_leaves)
    assert all(isinstance(s, P) for s in spec_leaves)
    # the projection cores are actually sharded, not silently replicated
    core = {"tgn": ("wq", "wk", "wv", "w_out1", "w_out2"),
            "tgat": ("wq", "wk", "wv"), "dysat": ("wq", "wk"),
            "graphsage": ("w_self", "w_nbr"), "gat": ("w_dst", "w_nbr")}
    layer0 = specs["gnn"]["layers"][0]
    for leaf in core[model]:
        assert any(ax is not None for ax in layer0[leaf]), (leaf,
                                                           layer0[leaf])
    assert any(ax is not None for ax in specs["head"]["w1"])
    if cfg.use_memory:
        assert any(ax is not None for ax in specs["memory"]["w_z"])

    shardings = sh.named_shardings(mesh, specs)
    placed = jax.device_put(params, shardings)
    for a, b in zip(param_leaves, jax.tree_util.tree_leaves(placed)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
    # at least one leaf is genuinely distributed over the mesh
    assert any(not l.sharding.is_fully_replicated
               for l in jax.tree_util.tree_leaves(placed))


@needs8
def test_named_shardings_drops_absent_axes():
    mesh = _mesh24()
    tree = {"a": P(("pod", "data"), None), "b": P(None, "model")}
    out = sh.named_shardings(mesh, tree)
    assert out["a"].spec == P(("data",), None) or \
        out["a"].spec == P("data", None)
    assert out["b"].spec == P(None, "model")
    assert isinstance(out["a"], NamedSharding)
