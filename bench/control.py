#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, and the control's
verdict under that limit.  Needs a TPU (``bench/tests/test_bench_control.py``
runs the same at a tiny size on the CPU).

    python bench/control.py --workload bsr50-decode --seeds 1,2,3 --seconds 30

For each seed, in one process: build the cell's engine with that seed's
weights, serve one window of the cell's own traffic, and judge the
finished requests twice with the harness's own check
(``serve.correctness``) under the cell's committed limits
(``bench/limits/<cell>.json``):

* ``program``: the served tokens, as a run judges them;
* ``control``: the reference put in the program's place at the next lower
  precision (float8 matmul inputs), over the same prompts and served
  tokens -- the gap of the token it puts first.

One JSON line per seed, with each side's ``correct`` and its checks.  The
limit goes above the largest program reading and below the smallest
control reading, so a sound program comes out correct and the control not.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(bench, cell_name: str, seed: int, seconds: float) -> dict:
    """Serve one window of the cell for ``seed``; judge the program's
    tokens and the control's over the same sampled requests."""
    from bench import model, serve, traffic
    from bench.system import Server

    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    cfg = model.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    server = Server(cfg, cell["config"], mix, seed)
    serve.warm_up(server, mix, seed)
    reqs = traffic.generate(mix, cfg["vocab_size"], seed, seconds)
    records, _, _, _, counters = serve.serve(server, reqs, seconds)
    server.close()
    server = None
    gc.collect()
    limits = serve.load_limits(cell_name)
    out = {"cell": cell_name, "seed": seed}
    for side in ("program", "control"):
        checks = serve.correctness(cfg, mix, seed, records, counters["end"],
                                   limits, lambda s: None,
                                   control=side == "control")
        out[side] = {"correct": all(c["ok"] for c in checks.values()),
                     "checks": {k: {"value": c["value"], "limit": c["limit"]}
                                for k, c in checks.items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py needs a TPU", file=sys.stderr)
        return 2
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = readings(bench, args.workload, seed, args.seconds)
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # run as a script: the checkout's root heads the path, then the program
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.exit(main())
