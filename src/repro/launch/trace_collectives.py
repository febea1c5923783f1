import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Collective-traffic trace for one dry-run cell: aggregates per-device
result bytes of every collective by (op kind, originating op_name) — the
§Perf microscope.

  PYTHONPATH=src python -m repro.launch.trace_collectives --arch X \
      --shape train_4k [--overrides k=v,...] [--top 20]
"""
import argparse
import re
import sys
from collections import Counter


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--overrides", default="")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()

    # reuse the dryrun cell builder up to `compiled`
    from repro.launch import dryrun as dr

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import SHAPES, get_config, input_specs
    from repro.distributed.sharding import axis_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import _shape_bytes, parse_collectives
    from repro.launch.specs import cell_shardings, rules_for_cell, tree_named
    from repro.models.transformer import init_params
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedule import warmup_cosine
    from repro.train.train_step import (
        init_train_state, make_decode_step, make_prefill_step, make_train_step)

    cfg = get_config(args.arch)
    ov = dr._parse_overrides(args.overrides)
    if ov:
        cfg = cfg.replace(**ov)
    cell = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    specs = input_specs(cfg, cell)
    opt_cfg = AdamWConfig(use_master=cfg.param_dtype != "float32")

    if cell.kind == "train":
        state_shapes = jax.eval_shape(
            lambda: init_train_state(init_params(jax.random.PRNGKey(0), cfg), opt_cfg))
    else:
        state_shapes = {"params": jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))}
    sh = cell_shardings(cfg, cell, mesh, args.multi_pod, specs,
                        state_shapes=state_shapes)
    rules = rules_for_cell(cell, mesh, args.multi_pod)
    with jax.set_mesh(mesh), axis_rules(rules):
        if cell.kind == "train":
            fn = jax.jit(make_train_step(cfg, opt_cfg, warmup_cosine(3e-4, 100, 10000)),
                         in_shardings=(tree_named(sh["state"], mesh),
                                       tree_named(sh["batch"], mesh)),
                         out_shardings=(tree_named(sh["state"], mesh), None))
            compiled = fn.lower(state_shapes, specs["batch"]).compile()
        elif cell.kind == "prefill":
            fn = jax.jit(make_prefill_step(cfg),
                         in_shardings=(tree_named(sh["params"], mesh),
                                       tree_named(sh["batch"], mesh)))
            compiled = fn.lower(state_shapes["params"], specs["batch"]).compile()
        else:
            cache_sh = tree_named(sh["caches"], mesh)
            fn = jax.jit(make_decode_step(cfg),
                         in_shardings=(tree_named(sh["params"], mesh), cache_sh,
                                       tree_named(sh["batch"], mesh),
                                       NamedSharding(mesh, P())),
                         out_shardings=(None, cache_sh))
            compiled = fn.lower(state_shapes["params"], specs["caches"],
                                specs["batch"], specs["cache_len"]).compile()

    txt = compiled.as_text()
    agg = Counter()
    pat = re.compile(
        r"=\s+(?:\(([^)]*)\)|(\S+))\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(")
    for line in txt.splitlines():
        m = pat.search(line)
        if not m or "-done" in line:
            continue
        ts = m.group(1) or m.group(2)
        op = m.group(3)
        meta = re.search(r'op_name="([^"]*)"', line)
        name = (meta.group(1) if meta else "?")[:100]
        agg[(op, name)] += _shape_bytes(ts)

    ops = parse_collectives(txt, mesh.shape["model"])
    wire = sum(o.wire_bytes for o in ops)
    print(f"total collective result bytes/dev: "
          f"{sum(agg.values())/1e9:.2f} GB; modeled wire: {wire/1e9:.2f} GB")
    for (op, name), nb in agg.most_common(args.top):
        print(f"{nb/1e9:8.3f}GB {op:18s} {name}")
    ca = compiled.cost_analysis()
    print(f"flops/dev={ca['flops']:.3e} bytes/dev={ca.get('bytes accessed',0):.3e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
