"""EP (shard_map all-to-all) MoE path == dense path, on 8 fake devices.

Runs in a subprocess because the placeholder-device XLA flag must be set
before jax initializes (same rule as the dry-run).
"""
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.dist.sharding import ShardingRules, sharding_ctx
from repro.models.moe import _moe_apply_dense, moe_apply, moe_init

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = ShardingRules({
    "batch": ("data",), "seq_act": "model", "expert": "model",
    "fsdp": None, "embed_fsdp": None, "moe_fsdp": None, "tp": None,
    "vocab": None, "embed_act": None,
})

moe = MoEConfig(num_experts=8, top_k=2, expert_d_ff=32,
                capacity_factor=8.0)   # big cf => no drops => exact match
key = jax.random.PRNGKey(0)
params = moe_init(key, moe, 16, "swiglu")
B, S, d = 4, 16, 16
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32)

with sharding_ctx(mesh, rules):
    x_sh = jax.device_put(x, NamedSharding(mesh, P(("data",), "model",
                                                   None)))
    y_ep, aux_ep = jax.jit(
        lambda p, xx: moe_apply(p, xx, moe, "swiglu"))(params, x_sh)
    y_dn, aux_dn = jax.jit(
        lambda p, xx: _moe_apply_dense(p, xx, moe, "swiglu"))(params, x)

np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dn),
                           rtol=2e-4, atol=2e-4)
# lb-loss: EP computes per-shard balance then averages (Switch's
# per-device convention) vs the dense path's global statistic — close
# but not identical by definition
assert abs(float(aux_ep["moe_lb_loss"]) - float(aux_dn["moe_lb_loss"])) \
    < 0.35 * float(aux_dn["moe_lb_loss"])
assert float(aux_ep["moe_drop_frac"]) == 0.0

# gradients flow and match
def loss_ep(p, xx):
    y, _ = moe_apply(p, xx, moe, "swiglu")
    return jnp.sum(y ** 2)

def loss_dn(p, xx):
    y, _ = _moe_apply_dense(p, xx, moe, "swiglu")
    return jnp.sum(y ** 2)

with sharding_ctx(mesh, rules):
    g_ep = jax.jit(jax.grad(loss_ep))(params, x_sh)
    g_dn = jax.jit(jax.grad(loss_dn))(params, x)
for k in ("w_up", "w_down", "router"):
    np.testing.assert_allclose(np.asarray(g_ep[k]), np.asarray(g_dn[k]),
                               rtol=3e-3, atol=3e-3)
print("EP==DENSE OK")
"""


def test_ep_matches_dense_on_fake_mesh(subprocess_env):
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=subprocess_env,
        capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EP==DENSE OK" in r.stdout
