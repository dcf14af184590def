"""Context-parallel shard_map attention == blocked attention (8 fake
devices, subprocess for the placeholder-device flag)."""
import subprocess
import sys

_SCRIPT = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.dist.sharding import ShardingRules, sharding_ctx
from repro.models.layers import blocked_attention
from repro.models.transformer_lm import _cp_attention_shard_map

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = ShardingRules({"batch": ("data",), "seq_act": "model"})

B, S, Hq, Hkv, D = 4, 64, 8, 4, 16
kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(kq, (B, S, Hq, D), jnp.float32)
k = jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
v = jax.random.normal(kv, (B, S, Hkv, D), jnp.float32)

for causal in (True, False):
    ref = blocked_attention(q, k, v, causal=causal, q_chunk=16,
                            kv_chunk=16)
    with sharding_ctx(mesh, rules):
        sh = NamedSharding(mesh, P(("data",), "model", None, None))
        qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
        got = jax.jit(lambda a, b, c: _cp_attention_shard_map(
            a, b, c, causal=causal))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

# gradients: dk must flow correctly through the all-gather transpose
def loss_cp(qq, kk_, vv):
    with sharding_ctx(mesh, rules):
        return jnp.sum(_cp_attention_shard_map(qq, kk_, vv,
                                               causal=True) ** 2)

def loss_ref(qq, kk_, vv):
    return jnp.sum(blocked_attention(qq, kk_, vv, causal=True,
                                     q_chunk=16, kv_chunk=16) ** 2)

with sharding_ctx(mesh, rules):
    g_cp = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
g_rf = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
for a, b in zip(g_cp, g_rf):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3,
                               atol=3e-3)
print("CP==REF OK")
'''


def test_cp_attention_matches_blocked(subprocess_env):
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=subprocess_env,
        capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CP==REF OK" in r.stdout
