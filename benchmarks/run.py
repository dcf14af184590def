"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.py).

The ``multihost`` bench launches CPU-forced worker processes (a
multi-process simulation of a fleet, ``repro.launch.multihost``); this
process has already touched JAX, so on an accelerator host that bench
is refused rather than run on the CPU under a chip's name."""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import get_logger

log = get_logger("bench")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of bench names to run")
    args = ap.parse_args()

    from benchmarks import (bench_access_patterns, bench_block_sizing,
                            bench_cache, bench_continuous,
                            bench_distributed, bench_graph_update,
                            bench_multihost, bench_roofline,
                            bench_sampling, bench_scaling,
                            bench_serving)
    benches = {
        "graph_update": bench_graph_update.run,      # Tab.2 / Fig.8
        "block_sizing": bench_block_sizing.run,      # Tab.6 / Fig.12
        "sampling": bench_sampling.run,              # Fig.9 / Fig.13
        "cache": bench_cache.run,                    # Fig.14
        "access_patterns": bench_access_patterns.run,  # Fig.5 / Tab.4
        "continuous": bench_continuous.run,          # Fig.8/10/11
        "distributed": bench_distributed.run,        # Fig.6 / §5
        "multihost": bench_multihost.run,            # §5 (real processes)
        "scaling": bench_scaling.run,                # Fig.15 / Tab.7
        "roofline": bench_roofline.run,              # deliverable (g)
        "serving": bench_serving.run,                # online serving wing
    }
    if args.only is not None and not args.only:
        log.error("--only given without bench names; available: "
                  f"{', '.join(benches)}")
        sys.exit(2)
    unknown = set(args.only or []) - benches.keys()
    if unknown:
        log.error(f"unknown bench names: {', '.join(sorted(unknown))}; "
                  f"available: {', '.join(benches)}")
        sys.exit(2)

    enable_compile_cache()
    import jax
    backend = jax.default_backend()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if args.only and name not in args.only:
            continue
        t0 = time.time()
        try:
            if name == "multihost" and backend != "cpu":
                raise RuntimeError(
                    f"refused on {backend}: the multihost bench spawns "
                    "CPU-forced workers from a process that holds the "
                    "accelerator (a CPU simulation, not a chip run)")
            fn()
        except Exception as e:  # keep the harness going, surface failure
            print(f"{name}/FAILED,0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            import traceback
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
        log.info(f"{name} done in {time.time() - t0:.1f}s")
    if failed:
        log.error(f"FAILED: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
