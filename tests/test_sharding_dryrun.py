"""Small-mesh (8 fake devices) dry-run smoke: the production sharding specs
lower+compile for a reduced config.  Runs in a subprocess because the fake
device count must be set before jax initializes."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config, input_specs, make_smoke
    from repro.configs.base import ShapeCell
    from repro.distributed.sharding import axis_rules
    from repro.launch.mesh import make_test_mesh
    from repro.launch.specs import cell_shardings, rules_for_cell, tree_named
    from repro.models.transformer import init_params
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedule import constant_lr
    from repro.train.train_step import init_train_state, make_train_step, make_decode_step
    from repro.models.transformer import init_caches

    arch = %(arch)r
    cfg = make_smoke(get_config(arch), d_model=256, n_heads=4, kv_heads=2,
                     head_dim=64, vocab=512)
    mesh = make_test_mesh((4, 2), ("data", "model"))
    cell = ShapeCell("t", "train", 64, 8)
    specs = input_specs(cfg, cell)
    opt_cfg = AdamWConfig(use_master=False)
    state_shapes = jax.eval_shape(
        lambda: init_train_state(init_params(jax.random.PRNGKey(0), cfg), opt_cfg))
    sh = cell_shardings(cfg, cell, mesh, False, specs, state_shapes=state_shapes)
    rules = rules_for_cell(cell, mesh, False)
    with jax.set_mesh(mesh), axis_rules(rules):
        step = make_train_step(cfg, opt_cfg, constant_lr(1e-3))
        fn = jax.jit(step,
                     in_shardings=(tree_named(sh["state"], mesh),
                                   tree_named(sh["batch"], mesh)),
                     out_shardings=(tree_named(sh["state"], mesh), None))
        compiled = fn.lower(state_shapes, specs["batch"]).compile()
        ca = compiled.cost_analysis()
        assert ca["flops"] > 0

        # decode cell too
        dcell = ShapeCell("d", "decode", 64, 8)
        dspecs = input_specs(cfg, dcell)
        dsh = cell_shardings(cfg, dcell, mesh, False, dspecs,
                             state_shapes={"params": state_shapes["params"]})
        dstep = make_decode_step(cfg)
        dfn = jax.jit(dstep,
                      in_shardings=(tree_named(dsh["params"], mesh),
                                    tree_named(dsh["caches"], mesh),
                                    tree_named(dsh["batch"], mesh),
                                    NamedSharding(mesh, P())),
                      out_shardings=(None, tree_named(dsh["caches"], mesh)))
        dcompiled = dfn.lower(state_shapes["params"], dspecs["caches"],
                              dspecs["batch"], dspecs["cache_len"]).compile()
    print(json.dumps({"ok": True, "flops": ca["flops"]}))
""")

ARCHS = ["qwen1.5-0.5b", "granite-moe-1b-a400m", "jamba-v0.1-52b", "xlstm-350m"]


@pytest.mark.parametrize("arch", ARCHS)
def test_small_mesh_lower_compile(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"arch": arch}],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["flops"] > 0


_MOE_EQUIV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import axis_rules, make_train_rules
    from repro.launch.mesh import make_test_mesh
    from repro.models.moe import moe_apply, moe_init
    from repro.models.moe_alltoall import moe_alltoall_apply

    mesh = make_test_mesh((4, 2), ("data", "model"))
    E, K, D, F = 4, 2, 32, 64
    p = moe_init(jax.random.PRNGKey(0), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, D))
    kw = dict(num_experts=E, top_k=K, capacity_factor=8.0)  # no drops

    with jax.set_mesh(mesh), axis_rules(make_train_rules(False)):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        ps = jax.tree.map(lambda a: jax.device_put(a), p)
        y_ref, aux_ref = jax.jit(lambda pp, xx: moe_apply(pp, xx, **kw))(ps, xs)
        y_a2a, aux_a2a = jax.jit(
            lambda pp, xx: moe_alltoall_apply(pp, xx, **kw))(ps, xs)
    err = float(jnp.abs(y_ref - y_a2a).max())
    aerr = abs(float(aux_ref) - float(aux_a2a))
    print(json.dumps({"err": err, "aux_err": aerr}))
    assert err < 1e-3, err
    assert aerr < 1e-3, aerr
""")


def test_moe_alltoall_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _MOE_EQUIV],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]


_MOE_A2A_PACKED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (BlockingSpec, apply_masks, build_structures,
                            masks_from_knapsack)
    from repro.core.packing import BSRPlanes
    from repro.distributed.sharding import axis_rules, make_train_rules
    from repro.launch.mesh import make_test_mesh
    from repro.models.moe import moe_apply, moe_init
    from repro.models.moe_alltoall import moe_alltoall_apply
    from repro.sparse import pack_params

    mesh = make_test_mesh((4, 2), ("data", "model"))
    E, K, D, F = 4, 2, 32, 64
    p = moe_init(jax.random.PRNGKey(0), D, F, E)
    # prune ~half the expert tiles, pack to BSRPlanes (router stays dense)
    structures = build_structures(p, BlockingSpec(bk=16, bn=16), min_size=256)
    rng = np.random.default_rng(0)
    sel = (rng.uniform(size=structures.total_structures) < 0.6
           ).astype(np.float32)
    masks = masks_from_knapsack(p, structures, sel)
    masked = apply_masks(p, masks)
    packed = pack_params(p, masks, structures)
    assert isinstance(packed["experts_up"], BSRPlanes)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, D))
    kw = dict(num_experts=E, top_k=K, capacity_factor=8.0)  # no drops

    with jax.set_mesh(mesh), axis_rules(make_train_rules(False)):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        # masked-dense GSPMD path is the oracle; the packed tree runs the
        # fused zero-skipping expert FFN behind the all_to_all dispatch
        y_ref, aux_ref = jax.jit(lambda pp, xx: moe_apply(pp, xx, **kw))(masked, xs)
        y_a2a, aux_a2a = jax.jit(
            lambda pp, xx: moe_alltoall_apply(pp, xx, **kw))(packed, xs)
    err = float(jnp.abs(y_ref - y_a2a).max())
    aerr = abs(float(aux_ref) - float(aux_a2a))
    print(json.dumps({"err": err, "aux_err": aerr}))
    assert err < 1e-3, err
    assert aerr < 1e-3, aerr
""")


def test_moe_alltoall_packed_equivalence():
    """BSRPlanes-packed expert weights through the explicit all-to-all
    dispatch (2-way expert sharding) match the masked-dense GSPMD MoE —
    the packed MoE all-to-all path of DESIGN.md §8."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _MOE_A2A_PACKED],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
