import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ The two lines above MUST be the first lines of this module, before ANY
# other import (jax locks the device count at first init). This module is
# the ONLY place the 512-placeholder-device env is set; smoke tests see
# the real single CPU device.

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
# derive the three roofline terms from the compiled artifact.
#
# Usage:
#     python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
#     python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --multi-pod
#     python -m repro.launch.dryrun --all --jobs 4          # subprocess batch
#     ... [--rule seq_act=model] [--save-hlo]               # perf-pass knobs

import argparse
import json
import re
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _cell_json(arch: str, shape: str, mesh_kind: str, tag: str) -> Path:
    suffix = f"__{tag}" if tag else ""
    return ARTIFACTS / f"{arch}__{shape}__{mesh_kind}{suffix}.json"


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS + parameter accounting
# ---------------------------------------------------------------------------


def count_params(cfg) -> Tuple[int, int]:
    """(total, active) parameter counts from the param spec tree."""
    import jax
    from repro.models.lm_zoo import param_specs

    specs = param_specs(cfg)
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(specs)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        path_str = "/".join(str(getattr(p, "key", "")) for p in path)
        if cfg.moe is not None and re.search(r"w_(gate|up|down)$", path_str) \
                and leaf.ndim == 4:  # stacked experts (L, E, in, out)
            active += n * cfg.moe.top_k // cfg.moe.num_experts
        else:
            active += n
    return total, active


def model_flops(cfg, shape) -> float:
    """Assignment formula: 6*N*D train (N=active for MoE), 2*N*D inference."""
    _, active = count_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * active * tokens


# ---------------------------------------------------------------------------
# Sharding assembly for step inputs/outputs
# ---------------------------------------------------------------------------


def _dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def _axis_size(mesh, names) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= sizes.get(a, 1)
    return n


def batch_specs(cfg, shape, mesh, multi_pod: bool):
    """PartitionSpecs for the input batch dict."""
    from jax.sharding import PartitionSpec as P
    dp = _dp_axes(multi_pod)
    B = shape.global_batch
    dp = dp if B % _axis_size(mesh, dp) == 0 else None
    tok = P(dp, None)
    if cfg.input_kind == "tokens":
        return {"tokens": tok}
    out = {"frames": P(dp, None, None)}
    if shape.kind == "train":
        out["labels"] = tok
        out["mask"] = tok
    return out


def decode_state_specs_tree(cfg, state_specs, mesh, multi_pod: bool):
    from jax.sharding import PartitionSpec as P
    import jax
    dp = _dp_axes(multi_pod)
    tp = "model"
    tp_n = _axis_size(mesh, tp)

    def one(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        shp = leaf.shape
        nd = len(shp)

        def dpx(dim):
            return dp if shp[dim] % _axis_size(mesh, dp) == 0 else None

        def tpx(dim):
            return tp if shp[dim] % tp_n == 0 else None

        if name == "pos":
            return P()
        if name in ("k", "v"):           # (..., B, S, H, D)
            # Prefer head sharding; when GQA kv-heads don't divide TP,
            # shard the context dim instead (flash-decoding split-KV:
            # GSPMD turns the softmax reduction into small collectives).
            if shp[nd - 2] % tp_n == 0:
                return P(*([None] * (nd - 4) + [dpx(nd - 4), None,
                                                tp, None]))
            return P(*([None] * (nd - 4) + [dpx(nd - 4), tpx(nd - 3),
                                            None, None]))
        if name == "conv":               # (..., B, K-1, C)
            return P(*([None] * (nd - 3) + [dpx(nd - 3), None,
                                            tpx(nd - 1)]))
        if name == "h":
            if cfg.ssm is not None and cfg.ssm.version == 2:
                #  (..., B, H, N, P)
                return P(*([None] * (nd - 4) + [dpx(nd - 4), tpx(nd - 3),
                                                None, None]))
            #  (..., B, Din, N)
            return P(*([None] * (nd - 3) + [dpx(nd - 3), tpx(nd - 2),
                                            None]))
        return P()

    return jax.tree_util.tree_map_with_path(one, state_specs)


def optimizer_state_specs(cfg, opt_shapes, pspecs):
    """Mirror parameter specs onto optimizer state (AdamW / Adafactor)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.train.optimizer import AdamWState

    def pad(spec, ndim):
        t = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
        return t

    if cfg.optimizer == "adamw":
        return AdamWState(step=P(), mu=pspecs, nu=pspecs)

    # adafactor: factored leaves are (row, col) tuples
    def one(pspec, shape_leaf):
        if isinstance(shape_leaf, tuple):  # (row, col) SDS pair
            row_sds, col_sds = shape_leaf
            nd = len(row_sds.shape) + 1
            t = pad(pspec, nd)
            return (P(*t[:-1]), P(*(t[:-2] + (t[-1],))))
        return pspec

    mu = jax.tree.map(one, pspecs, opt_shapes.mu,
                      is_leaf=lambda x: isinstance(x, P))
    return AdamWState(step=P(), mu=mu, nu=None)


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rule_overrides: Dict[str, Any], save_hlo: bool,
             tag: str = "") -> Dict[str, Any]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_arch
    from repro.dist.sharding import (default_rules, named_shardings,
                                     param_partition_specs, sharding_ctx)
    from repro.launch import hlo_cost
    from repro.launch.mesh import HW, make_production_mesh
    from repro.models import lm_zoo

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh_kind = "multi" if multi_pod else "single"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    rules = default_rules(multi_pod=multi_pod)
    if cfg.family in ("ssm", "hybrid") and shape.kind == "train":
        # mamba blocks are channel/head-separable: TP over d_inner/heads is
        # fully local; sequence-CP would shard the scan's time axis.
        rules = rules.override(seq_act=None, tp="model", fsdp=("data",))
    if shape.kind != "train":
        # Inference topology: pure TP within each data-replica group
        # (weights replicated across 'data', sharded over 'model'); FSDP
        # weight-gather per decode step would dominate the step.
        rules = rules.override(fsdp=None, embed_fsdp=None, tp="model",
                               seq_act=None, vocab="model")
    if rule_overrides:
        fixed = {}
        for k, v in rule_overrides.items():
            if v in ("None", ""):
                fixed[k] = None
            elif "," in v:
                fixed[k] = tuple(v.split(","))
            else:
                fixed[k] = v
        rules = rules.override(**fixed)

    res: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": n_chips, "kind": shape.kind, "tag": tag,
        "rules": {k: v for k, v in rules.table.items()},
    }

    t0 = time.time()
    with sharding_ctx(mesh, rules):
        pspecs = param_partition_specs(lm_zoo.param_specs(cfg), rules)
        bspecs = batch_specs(cfg, shape, mesh, multi_pod)
        specs_in = lm_zoo.input_specs(cfg, shape)

        if shape.kind == "train":
            optimizer = lm_zoo.make_optimizer(cfg)
            state_sds = lm_zoo.train_state_specs(cfg, optimizer)
            ospecs = optimizer_state_specs(cfg, state_sds["opt"], pspecs)
            in_specs = ({"params": pspecs, "opt": ospecs}, bspecs)
            metrics_sds = jax.eval_shape(
                lm_zoo.make_loss_fn(cfg), state_sds["params"],
                specs_in["batch"])[1]
            mspecs = jax.tree.map(lambda _: P(), metrics_sds)
            out_specs = ({"params": pspecs, "opt": ospecs}, mspecs)
            step = lm_zoo.make_train_step(cfg, optimizer)
            args = (state_sds, specs_in["batch"])
        elif shape.kind == "prefill":
            import jax.numpy as jnp
            bf16_params = lm_zoo.param_specs(cfg, dtype=jnp.bfloat16)
            dp = _dp_axes(multi_pod)
            dpv = dp if shape.global_batch % _axis_size(mesh, dp) == 0 \
                else None
            step = lm_zoo.make_prefill_step(cfg)
            vocab_ax = (rules.table.get("vocab")
                        if cfg.vocab % _axis_size(
                            mesh, rules.table.get("vocab")) == 0 else None)
            if cfg.is_encoder:
                logits_spec = P(dpv, None, vocab_ax)
            else:
                logits_spec = P(dpv, vocab_ax)
            if cfg.is_encoder:
                out_specs = (logits_spec, P())
            else:
                st_sds = jax.eval_shape(step, bf16_params,
                                        specs_in["batch"])[1]
                out_specs = (logits_spec, decode_state_specs_tree(
                    cfg, st_sds, mesh, multi_pod))
            in_specs = (pspecs, bspecs)
            args = (bf16_params, specs_in["batch"])
        else:  # decode
            import jax.numpy as jnp
            bf16_params = lm_zoo.param_specs(cfg, dtype=jnp.bfloat16)
            dp = _dp_axes(multi_pod)
            dpv = dp if shape.global_batch % _axis_size(mesh, dp) == 0 \
                else None
            step = lm_zoo.make_serve_step(cfg)
            if cfg.is_encoder:
                raise ValueError("decode shape on encoder arch")
            dstate_specs = decode_state_specs_tree(
                cfg, specs_in["dstate"], mesh, multi_pod)
            vocab_ax = (rules.table.get("vocab")
                        if cfg.vocab % _axis_size(
                            mesh, rules.table.get("vocab")) == 0 else None)
            in_specs = (pspecs, dstate_specs, P(dpv, None))
            out_specs = (P(dpv, vocab_ax), dstate_specs)
            args = (bf16_params, specs_in["dstate"],
                    jax.ShapeDtypeStruct((shape.global_batch, 1),
                                         jax.numpy.int32))

        in_sh = named_shardings(mesh, in_specs)
        out_sh = named_shardings(mesh, out_specs)
        jf = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jf.lower(*args)
        res["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t1, 2)

    # ---- memory analysis (per device) ----
    ma = compiled.memory_analysis()
    res["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "hbm_frac": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        / HW["hbm_bytes"],
    }

    # ---- xla's own cost analysis (known loop-undercount; kept for ref) ----
    try:
        ca = compiled.cost_analysis()
        res["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", -1)),
            "bytes_accessed": float(ca.get("bytes accessed", -1)),
        }
    except Exception as e:  # pragma: no cover
        res["xla_cost_analysis"] = {"error": str(e)}

    # ---- our scaled HLO cost (per chip) ----
    txt = compiled.as_text()
    cost = hlo_cost.total_cost(txt)
    res["hlo"] = {k: float(v) for k, v in cost.items()}
    res["top_collectives"] = hlo_cost.collective_breakdown(txt)[:12]
    if save_hlo:
        hdir = ARTIFACTS.parent / "hlo"
        hdir.mkdir(parents=True, exist_ok=True)
        (hdir / f"{arch}__{shape_name}__{mesh_kind}"
         f"{('__' + tag) if tag else ''}.hlo.txt").write_text(txt)

    # ---- roofline terms ----
    compute_s = cost["flops"] / HW["peak_flops_bf16"]
    memory_s = cost["bytes"] / HW["hbm_bw"]
    collective_s = cost["collective_bytes"] / HW["ici_bw_per_link"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    total_p, active_p = count_params(cfg)
    res.update({
        "roofline": terms,
        "dominant": dominant,
        "model_flops_global": mf,
        "hlo_flops_global": cost["flops"] * n_chips,
        "model_to_hlo_flops": mf / max(cost["flops"] * n_chips, 1.0),
        "params_total": total_p,
        "params_active": active_p,
        "step_time_bound_s": max(terms.values()),
        "roofline_frac": (mf / n_chips / HW["peak_flops_bf16"])
        / max(max(terms.values()), 1e-30),
        "ok": True,
    })
    return res


# ---------------------------------------------------------------------------
# CLI / batch driver
# ---------------------------------------------------------------------------


def _run_batch(jobs: int, multi_pod_only: Optional[bool], save_hlo: bool,
               archs: Optional[list] = None) -> None:
    from repro.configs import dryrun_cells
    cells = []
    for cfg, shape in dryrun_cells():
        if archs and cfg.name not in archs:
            continue
        for mp in ([False, True] if multi_pod_only is None
                   else [multi_pod_only]):
            out = _cell_json(cfg.name, shape.name,
                             "multi" if mp else "single", "")
            if out.exists():
                try:
                    if json.loads(out.read_text()).get("ok"):
                        continue
                except Exception:
                    pass
            cells.append((cfg.name, shape.name, mp))
    print(f"[dryrun] {len(cells)} cells to run, jobs={jobs}")
    procs: list = []
    for arch, shape, mp in cells:
        while len(procs) >= jobs:
            for p in procs[:]:
                if p.poll() is not None:
                    procs.remove(p)
            time.sleep(1.0)
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape]
        if mp:
            cmd.append("--multi-pod")
        if save_hlo:
            cmd.append("--save-hlo")
        print("[dryrun] start", arch, shape, "multi" if mp else "single",
              flush=True)
        procs.append(subprocess.Popen(
            cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
    for p in procs:
        p.wait()
    print("[dryrun] batch done")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", nargs="*", default=None)
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--rule", action="append", default=[],
                    help="logical=meshaxis override, e.g. seq_act=model")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix (perf runs)")
    args = ap.parse_args()

    ARTIFACTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        _run_batch(args.jobs,
                   multi_pod_only=(False if args.single_pod_only else None),
                   save_hlo=args.save_hlo, archs=args.archs)
        return

    overrides = dict(r.split("=", 1) for r in args.rule)
    mesh_kind = "multi" if args.multi_pod else "single"
    out = _cell_json(args.arch, args.shape, mesh_kind, args.tag)
    try:
        res = run_cell(args.arch, args.shape, args.multi_pod, overrides,
                       args.save_hlo, args.tag)
    except Exception as e:  # record failures as artifacts too
        import traceback
        res = {"arch": args.arch, "shape": args.shape, "mesh": mesh_kind,
               "tag": args.tag, "ok": False, "error": str(e),
               "traceback": traceback.format_exc()}
    out.write_text(json.dumps(res, indent=2, default=str))
    if res.get("ok"):
        t = res["roofline"]
        print(f"[dryrun] {args.arch} {args.shape} {mesh_kind}: "
              f"compute={t['compute_s']:.4f}s memory={t['memory_s']:.4f}s "
              f"collective={t['collective_s']:.4f}s "
              f"dominant={res['dominant']} "
              f"roofline_frac={res['roofline_frac']:.3f} "
              f"(lower {res['lower_s']}s compile {res['compile_s']}s)")
    else:
        print(f"[dryrun] FAILED {args.arch} {args.shape} {mesh_kind}: "
              f"{res['error']}")
        sys.exit(1)


if __name__ == "__main__":
    main()
