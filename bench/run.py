#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/``), traffic mix
(``bench/traffic/``) and metrics (``bench/metrics/``) are found by name
from ``BENCHMARK.json`` at the root of the checkout.  The run needs an
accelerator: with JAX's first device not a TPU, or fewer devices than
the cell asks for, it exits 2 and prints no result.  JAX's persistent
compilation cache lives in ``.jax_cache/`` in the checkout unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the last seconds of the window with the profiler and prints its
per-layer metrics, the device's busy time and a breakdown.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the checkout's root, not bench/, heads the path: bench's modules are
# imported as the ``bench`` package and the program from src/
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: {sorted(cells)}",
              file=sys.stderr)
        return 2
    if args.seed < 0:
        print("--seed must be a whole number >= 0", file=sys.stderr)
        return 2

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    want = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench/run.py needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2

    from bench.serve import run_cell

    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
