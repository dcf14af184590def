"""Dry-run machinery integration: reduced configs of every family lower,
compile and produce coherent roofline terms on a small fake mesh
(subprocess for the placeholder-device flag). This is the CI-sized
version of deliverable (e)."""
import json
import subprocess
import sys

_SCRIPT = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.dist.sharding import (default_rules, named_shardings,
                                 param_partition_specs, sharding_ctx)
from repro.launch import hlo_cost
from repro.models import lm_zoo

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
out = {}
for arch in ("yi-6b", "qwen3-moe-235b-a22b", "falcon-mamba-7b",
             "zamba2-2.7b", "hubert-xlarge"):
    cfg = get_arch(arch).reduced()
    rules = default_rules()
    if cfg.family in ("ssm", "hybrid"):
        rules = rules.override(seq_act=None, tp="model", fsdp=("data",))
    with sharding_ctx(mesh, rules):
        pspecs = param_partition_specs(lm_zoo.param_specs(cfg), rules)
        optimizer = lm_zoo.make_optimizer(cfg)
        state = lm_zoo.train_state_specs(cfg, optimizer)
        B, S = 8, 32
        if cfg.input_kind == "tokens":
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
            bspecs = {"tokens": P(("data",), None)}
        else:
            batch = {"frames": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                    jnp.bfloat16),
                     "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                     "mask": jax.ShapeDtypeStruct((B, S), jnp.bool_)}
            bspecs = {"frames": P(("data",), None, None),
                      "labels": P(("data",), None),
                      "mask": P(("data",), None)}
        from repro.launch.dryrun import optimizer_state_specs
        ospecs = optimizer_state_specs(cfg, state["opt"], pspecs)
        in_sh = named_shardings(mesh, ({"params": pspecs, "opt": ospecs},
                                       bspecs))
        step = lm_zoo.make_train_step(cfg, optimizer)
        compiled = jax.jit(step, in_shardings=in_sh).lower(
            state, batch).compile()
        cost = hlo_cost.total_cost(compiled.as_text())
        assert cost["flops"] > 0
        assert cost["bytes"] > 0
        out[arch] = {k: float(v) for k, v in cost.items()}
print("DRYRUN_SMALL " + json.dumps(out))
'''


def test_reduced_dryrun_all_families(subprocess_env):
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=subprocess_env,
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines()
            if l.startswith("DRYRUN_SMALL")][0]
    out = json.loads(line.split(" ", 1)[1])
    assert len(out) == 5
    # MoE cells should show collective traffic (the EP all-to-alls)
    assert out["qwen3-moe-235b-a22b"]["collective_bytes"] > 0
