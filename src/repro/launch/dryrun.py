import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("DRYRUN_XLA_EXTRA", "")
)
# ^^ MUST precede every other import (jax locks the device count on first
#    init).  Do NOT replicate this globally: tests/benches see 1 device.
# DRYRUN_XLA_EXTRA lets the grid driver trade CPU-backend codegen time for
# nothing we measure (cost analysis runs on optimized HLO, not emitted code).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. eval_shape's the full train/serve state (ShapeDtypeStruct only — no
     allocation),
  3. jits the step with explicit in/out shardings and ``.lower().compile()``s,
  4. records memory_analysis / cost_analysis / parsed collective schedule /
     roofline terms to JSON (incremental: existing results are skipped).

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out results/dryrun [--fresh-process] [--force]
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict


def _cell_id(arch: str, shape: str, multi_pod: bool, tag: str = "") -> str:
    base = f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"
    return f"{base}__{tag}" if tag else base


def _parse_overrides(spec: str) -> Dict[str, Any]:
    """'seq_sharded_acts=true,row_accum_dtype=bfloat16,attn_chunk=256'"""
    out: Dict[str, Any] = {}
    for item in filter(None, (spec or "").split(",")):
        k, v = item.split("=", 1)
        if v.lower() in ("true", "false"):
            out[k] = v.lower() == "true"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                out[k] = v
    return out


def run_cell(arch: str, shape: str, multi_pod: bool,
             overrides: Dict[str, Any] = None) -> Dict[str, Any]:
    """Lower+compile one cell; returns the JSON-able result record.
    JAX is imported here, in the process that compiles: under
    ``--fresh-process`` the parent only dispatches children and never
    initialises a backend of its own."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config, input_specs, cell_applicable
    from repro.distributed.sharding import axis_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import analyze_compiled
    from repro.launch.specs import cell_shardings, rules_for_cell, tree_named
    from repro.launch.supplements import supplements_for
    from repro.models.transformer import init_params
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import (
        init_train_state,
        make_decode_step,
        make_prefill_step,
        make_train_step,
    )

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    cell = SHAPES[shape]
    ok, reason = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    specs = input_specs(cfg, cell)
    opt_cfg = AdamWConfig(use_master=cfg.param_dtype != "float32")

    if cell.kind == "train":
        state_shapes = jax.eval_shape(
            lambda: init_train_state(init_params(jax.random.PRNGKey(0), cfg), opt_cfg)
        )
    else:
        state_shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg)
        )
        state_shapes = {"params": state_shapes}

    shardings = cell_shardings(cfg, cell, mesh, multi_pod, specs,
                               state_shapes=state_shapes)
    rules = rules_for_cell(cell, mesh, multi_pod)

    from repro.optim.schedule import warmup_cosine
    lr = warmup_cosine(3e-4, 100, 10000)

    with jax.set_mesh(mesh), axis_rules(rules):
        if cell.kind == "train":
            step = make_train_step(cfg, opt_cfg, lr)
            in_sh = (tree_named(shardings["state"], mesh),
                     tree_named(shardings["batch"], mesh))
            out_sh = (in_sh[0], None)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            lowered = fn.lower(state_shapes, specs["batch"])
        elif cell.kind == "prefill":
            step = make_prefill_step(cfg)
            in_sh = (tree_named(shardings["params"], mesh),
                     tree_named(shardings["batch"], mesh))
            fn = jax.jit(step, in_shardings=in_sh)
            lowered = fn.lower(state_shapes["params"], specs["batch"])
        else:  # decode
            step = make_decode_step(cfg)
            cache_sh = tree_named(shardings["caches"], mesh)
            in_sh = (tree_named(shardings["params"], mesh),
                     cache_sh,
                     tree_named(shardings["batch"], mesh),
                     NamedSharding(mesh, P()))
            out_sh = (None, cache_sh)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            lowered = fn.lower(state_shapes["params"], specs["caches"],
                               specs["batch"], specs["cache_len"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        supp = supplements_for(
            cfg, cell,
            model_size=mesh.shape["model"],
            dp_size=chips // mesh.shape["model"],
        )
        record = analyze_compiled(
            compiled, cfg, cell,
            mesh_name="2x16x16" if multi_pod else "16x16",
            chips=chips,
            default_group=mesh.shape["model"],
            supplements=supp,
        )

    out = record.to_dict()
    out.update({
        "status": "ok",
        "multi_pod": multi_pod,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    })
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--fresh-process", action="store_true",
                    help="run each cell in a subprocess (crash isolation)")
    ap.add_argument("--overrides", default="",
                    help="config overrides, e.g. seq_sharded_acts=true")
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    from repro.configs import SHAPES, list_archs

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi_pod in pods:
                cid = _cell_id(arch, shape, multi_pod, args.tag)
                path = os.path.join(args.out, cid + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip cached] {cid}")
                    continue
                print(f"[run] {cid}", flush=True)
                if args.fresh_process:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--mesh", "multi" if multi_pod else "single",
                           "--out", args.out, "--overrides", args.overrides,
                           "--tag", args.tag] + (["--force"] if args.force else [])
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=3600)
                    if r.returncode != 0:
                        failures += 1
                        err = {"arch": arch, "cell": shape, "multi_pod": multi_pod,
                               "status": "error",
                               "error": (r.stderr or r.stdout)[-4000:]}
                        with open(path, "w") as f:
                            json.dump(err, f, indent=2)
                        print(f"  FAILED (subprocess)", flush=True)
                    continue
                try:
                    rec = run_cell(arch, shape, multi_pod,
                                   _parse_overrides(args.overrides))
                except Exception as e:  # record, keep going
                    failures += 1
                    rec = {"arch": arch, "cell": shape, "multi_pod": multi_pod,
                           "status": "error", "error": traceback.format_exc()[-4000:]}
                    print(f"  FAILED: {e}", flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2, default=str)
                if rec.get("status") == "ok":
                    print(f"  ok: compile={rec['compile_s']}s "
                          f"dominant={rec['dominant']} "
                          f"compute={rec['compute_s']:.3e}s "
                          f"memory={rec['memory_s']:.3e}s "
                          f"coll={rec['collective_s']:.3e}s", flush=True)
                elif rec.get("status") == "skipped":
                    print(f"  skipped: {rec['reason']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
