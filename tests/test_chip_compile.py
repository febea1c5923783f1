"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode never checks Mosaic's block tiling, so these tests lower
and compile every serving kernel at published model widths against a
``v5e:2x2`` topology description: the TPU compiler runs on the host and
refuses what the chip would refuse (unaligned blocks, VMEM overflow).
Nothing executes.  Widths: qwen1.5-0.5b (H = K = 16, dh 64, d_ff 2816)
granite-moe-1b-a400m (H 16, K 8, 32 experts of d_ff 512) and
mellum2-12b-a2.5b (H 32, K 4, dh 128, 1024-token windows, held experts of
d 2304 x 896).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packing import BSRPlanes, BSRWeight
from repro.core.structures import BlockingSpec
from repro.kernels.block_sparse_matmul import (
    bsr_matmul_pallas,
    bsr_planes_matmul_pallas,
)
from repro.kernels.epilogue import Epilogue
from repro.kernels.moe_experts import moe_experts_pallas
from repro.kernels.paged_attention import (
    paged_attention_decode_pallas,
    paged_attention_prefill_pallas,
    paged_kv_write_pallas,
)

BLOCK = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile can be written to the persistent cache
    # but never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    """Lower + compile for the described chip; the Pallas kernel must be
    in the program as a Mosaic custom call."""
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


# (h, kvh): qwen1.5-0.5b MHA and granite-moe-1b-a400m GQA; page sizes
# at the sublane tiling of fp32 (8) and bf16 (16) pools
ATTN_WIDTHS = [(16, 16), (16, 8)]
POOLS = [(8, "float32"), (16, "float32"), (16, "bfloat16")]


def _decode_shapes():
    """(h, kvh, ps, pool dtype, b, max_pages, pool pages): every width x
    pool at 8 slots of 1024 context, and the decode cells' exact shape
    (16 slots, 40-page tables over a 641-page pool)."""
    for h, kvh in ATTN_WIDTHS:
        for ps, pool_dtype in POOLS:
            mp = 1024 // ps
            yield pytest.param(h, kvh, ps, pool_dtype, 8, mp, 8 * mp + 1,
                               id=f"{h}-{kvh}-{ps}-{pool_dtype}")
    yield pytest.param(16, 16, 16, "float32", 16, 40, 641,
                       id="decode-cells-b16-mp40-p641")


@pytest.mark.parametrize("h,kvh,ps,pool_dtype,b,mp,n_pages", _decode_shapes())
def test_paged_decode_compiles_for_v5e(one_chip, h, kvh, ps, pool_dtype, b,
                                       mp, n_pages):
    dh = 64
    args = (
        _sds((b, h, dh), "bfloat16", one_chip),
        _sds((b, kvh, dh), "bfloat16", one_chip),
        _sds((b, kvh, dh), "bfloat16", one_chip),
        _sds((n_pages, kvh, ps, dh), pool_dtype, one_chip),
        _sds((n_pages, kvh, ps, dh), pool_dtype, one_chip),
        _sds((b, mp), "int32", one_chip),
        _sds((b,), "int32", one_chip),
    )
    _compile(paged_attention_decode_pallas, *args)


@pytest.mark.parametrize("s,bm,q_offset", [
    (512, 512, 0),       # one query tile over a full-length prompt
    (200, 200, 0),       # prompt length off the sublane grid
    (512, 128, 0),       # bm-tiled query blocks
    (72, 72, 128),       # prefix-cache tail over shared pages
])
@pytest.mark.parametrize("h,kvh", ATTN_WIDTHS)
def test_paged_prefill_compiles_for_v5e(one_chip, h, kvh, s, bm, q_offset):
    dh, ps, max_seq = 64, 16, 1024
    mp = max_seq // ps
    n_pages = mp + 1

    def fn(q, kp, vp, tbl, ln):
        return paged_attention_prefill_pallas(
            q, kp, vp, tbl, ln, bm=bm, q_offset=q_offset)

    _compile(fn,
             _sds((1, s, h, dh), "bfloat16", one_chip),
             _sds((n_pages, kvh, ps, dh), "float32", one_chip),
             _sds((n_pages, kvh, ps, dh), "float32", one_chip),
             _sds((1, mp), "int32", one_chip),
             _sds((1,), "int32", one_chip))


def test_paged_kernels_refuse_untileable_page_size(one_chip):
    """A page size off the pool dtype's sublane tiling is refused up
    front with the reason, not deep inside Mosaic."""
    args = (
        _sds((2, 4, 64), "float32", one_chip),
        _sds((2, 4, 64), "float32", one_chip),
        _sds((2, 4, 64), "float32", one_chip),
        _sds((9, 4, 4, 64), "float32", one_chip),
        _sds((9, 4, 4, 64), "float32", one_chip),
        _sds((2, 4), "int32", one_chip),
        _sds((2,), "int32", one_chip),
    )
    with pytest.raises(ValueError, match="sublane tiling"):
        jax.jit(paged_attention_decode_pallas).lower(*args)


# the mellum2-mixed-backlog cell: 16 slots, 3072 context in 16-token
# pages (192-page tables over a 3073-page fp32 pool), 32 query heads over
# 4 KV heads of 128, 1024-token windows
MELLUM = dict(b=16, h=32, kvh=4, dh=128, ps=16, mp=192, pages=3073)


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_mellum2_paged_decode_compiles_for_v5e(one_chip, window):
    m = MELLUM
    pool = _sds((m["pages"], m["kvh"], m["ps"], m["dh"]), "float32", one_chip)
    args = (_sds((m["b"], m["h"], m["dh"]), "bfloat16", one_chip),
            _sds((m["b"], m["kvh"], m["dh"]), "bfloat16", one_chip),
            _sds((m["b"], m["kvh"], m["dh"]), "bfloat16", one_chip),
            pool, pool, _sds((m["b"], m["mp"]), "int32", one_chip),
            _sds((m["b"],), "int32", one_chip))
    _compile(lambda *a: paged_attention_decode_pallas(*a, window=window),
             *args)


@pytest.mark.parametrize("s", [256, 2560])
@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_mellum2_paged_prefill_compiles_for_v5e(one_chip, window, s):
    """The cell's shortest and longest prompts at the program's default
    512-token tile, which the kernel narrows to fit 8 query heads per KV
    head in scoped VMEM."""
    m = MELLUM
    pool = _sds((m["pages"], m["kvh"], m["ps"], m["dh"]), "float32", one_chip)
    _compile(lambda *a: paged_attention_prefill_pallas(*a, bm=512,
                                                       window=window),
             _sds((1, s, m["h"], m["dh"]), "bfloat16", one_chip), pool, pool,
             _sds((1, m["mp"]), "int32", one_chip),
             _sds((1,), "int32", one_chip))


@pytest.mark.parametrize("tm,tiles", [(16, 15), (128, 167)],
                         ids=["decode-16-rows", "prefill-2560-tokens"])
def test_moe_experts_compiles_for_v5e(one_chip, tm, tiles):
    """Held experts' grouped FFN at mellum2's widths (8 held experts of
    d 2304 x 896, bf16): a decode tick of 16 tokens and a 2560-token
    prefill, each at the static tile bound moe_serve gives them."""
    d, f, e = 2304, 896, 8
    _compile(lambda *a: moe_experts_pallas(*a, block_rows=tm),
             _sds((tiles * tm, d), "bfloat16", one_chip),
             _sds((e, d, f), "bfloat16", one_chip),
             _sds((e, d, f), "bfloat16", one_chip),
             _sds((e, f, d), "bfloat16", one_chip),
             _sds((tiles,), "int32", one_chip), _sds((1,), "int32", one_chip))


def _bsr_spec(k, n, sharding, planes=None):
    """Shape-only BSR weight at 50% tile density, 128x128 blocks."""
    gk, gn = k // BLOCK, n // BLOCK
    max_nnz = max(gk // 2, 1)
    nnz = gn * max_nnz
    lead = () if planes is None else (planes,)
    fields = dict(
        indices=_sds(lead + (gn, max_nnz), "int32", sharding),
        slots=_sds(lead + (gn, max_nnz), "int32", sharding),
        blocks=_sds(lead + (nnz, BLOCK, BLOCK), "bfloat16", sharding),
        flat_rows=_sds(lead + (nnz,), "int32", sharding),
        flat_cols=_sds(lead + (nnz,), "int32", sharding),
        blocking=BlockingSpec(bk=BLOCK, bn=BLOCK),
    )
    if planes is None:
        return BSRWeight(shape=(k, n), nnz_blocks=nnz, **fields)
    return BSRPlanes(shape=(planes, k, n), plane_nnz=(nnz,) * planes,
                     **fields)


@pytest.mark.parametrize("epilogue", [False, True])
@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n", [(1024, 2816), (2816, 1024)])
def test_bsr_matmul_compiles_for_v5e(one_chip, k, n, m, epilogue):
    w = _bsr_spec(k, n, one_chip)
    x = _sds((m, k), "bfloat16", one_chip)
    if not epilogue:
        _compile(lambda x, w: bsr_matmul_pallas(x, w), x, w)
        return
    # the fused MLP tail: bias, SwiGLU gate multiply, residual
    epi = Epilogue(bias=_sds((n,), "bfloat16", one_chip),
                   multiplier=_sds((m, n), "bfloat16", one_chip),
                   residual=_sds((m, n), "bfloat16", one_chip),
                   activation="silu")
    _compile(lambda x, w, e: bsr_matmul_pallas(x, w, epilogue=e), x, w, epi)


@pytest.mark.parametrize("m", [8, 256])
def test_bsr_planes_matmul_compiles_for_v5e(one_chip, m):
    e, k, n = 32, 1024, 512
    planes = _bsr_spec(k, n, one_chip, planes=e)
    x = _sds((e, m, k), "bfloat16", one_chip)
    epi = Epilogue(multiplier=_sds((e, m, n), "bfloat16", one_chip),
                   activation="silu")
    _compile(lambda x, w, ep: bsr_planes_matmul_pallas(x, w, epilogue=ep),
             x, planes, epi)


def _named_kernels(hlo: str, kernel: str) -> int:
    """Instructions of ``hlo`` that are the Pallas kernel ``kernel``, in
    the form the device trace names an operation and ``bench/trace.py``'s
    ``kernel_time`` matches it: ``%<kernel>.N = ... tpu_custom_call``."""
    rx = re.compile(rf'^%{kernel}(\.\d+)? = .*'
                    r'custom_call_target="tpu_custom_call"')
    return sum(bool(rx.match(line.strip().removeprefix("ROOT ")))
               for line in hlo.splitlines())


def _kernel_call(kernel, sharding):
    """(body, carry, rest) of one call of ``kernel``'s ``pallas_call``,
    with no jit of its own around it; the output is shaped like the
    carry.  A ``*_window`` attention kernel is the same call with a
    sliding window."""
    window = 64 if kernel.endswith("_window") else None
    if kernel.startswith("paged_attention_decode"):
        b, h, dh, ps, mp = 8, 16, 64, 16, 16
        pool = _sds((b * mp + 1, h, ps, dh), "float32", sharding)
        rest = (_sds((b, h, dh), "bfloat16", sharding),
                _sds((b, h, dh), "bfloat16", sharding), pool, pool,
                _sds((b, mp), "int32", sharding), _sds((b,), "int32", sharding))
        return (lambda q, *a: paged_attention_decode_pallas(
            q, *a, window=window).astype(q.dtype),
            _sds((b, h, dh), "bfloat16", sharding), rest)
    if kernel.startswith("paged_attention_prefill"):
        s, h, dh, ps, mp = 128, 16, 64, 16, 16
        pool = _sds((mp + 1, h, ps, dh), "float32", sharding)
        rest = (pool, pool, _sds((1, mp), "int32", sharding),
                _sds((1,), "int32", sharding))
        return (lambda q, *a: paged_attention_prefill_pallas(
            q, *a, window=window).astype(q.dtype),
            _sds((1, s, h, dh), "bfloat16", sharding), rest)
    if kernel == "paged_kv_write":
        b, h, dh, ps, mp = 8, 16, 64, 16, 16
        pool = _sds((b * mp + 1, h, ps, dh), "float32", sharding)
        rest = (_sds((b, h, dh), "bfloat16", sharding),
                _sds((b, h, dh), "bfloat16", sharding), pool,
                _sds((b, mp), "int32", sharding), _sds((b,), "int32", sharding))
        return (lambda kp, kn, vn, vp, *a: paged_kv_write_pallas(
            kn, vn, kp, vp, *a)[0], pool, rest)
    if kernel == "moe_experts":
        tm, tiles, d, f, e = 16, 4, 256, 128, 4
        rest = (_sds((e, d, f), "bfloat16", sharding),
                _sds((e, d, f), "bfloat16", sharding),
                _sds((e, f, d), "bfloat16", sharding),
                _sds((tiles,), "int32", sharding), _sds((1,), "int32", sharding))
        return (lambda x, *a: moe_experts_pallas(
            x, *a, block_rows=tm).astype(x.dtype),
            _sds((tiles * tm, d), "bfloat16", sharding), rest)
    if kernel == "bsr_matmul":
        return (bsr_matmul_pallas, _sds((8, 1024), "bfloat16", sharding),
                (_bsr_spec(1024, 1024, sharding),))
    e = 4
    return (bsr_planes_matmul_pallas, _sds((e, 8, 512), "bfloat16", sharding),
            (_bsr_spec(512, 512, sharding, planes=e),))


@pytest.mark.parametrize("kernel", ["paged_attention_decode",
                                    "paged_attention_prefill",
                                    "bsr_matmul", "bsr_planes_matmul",
                                    "paged_attention_decode_window",
                                    "paged_attention_prefill_window",
                                    "moe_experts", "paged_kv_write"])
def test_kernels_keep_their_names_in_the_compiled_program(one_chip, kernel):
    """Each Pallas kernel compiles to a ``tpu_custom_call`` instruction
    named by its ``pallas_call(name=...)``, not after whichever jit
    encloses it.  The device trace names an operation by its instruction,
    so this is the name the benchmark's kernel metrics find it by.  The
    kernel is called with no jit of its own, inside a scan in an outer
    jit: without ``name=`` its instruction takes the outer jit's name."""
    body, carry, rest = _kernel_call(kernel, one_chip)

    def chunk(x, *a):
        return jax.lax.scan(lambda c, _: (body(c, *a), None), x, None,
                            length=2)[0]

    hlo = jax.jit(chunk).lower(carry, *rest).compile().as_text()
    assert _named_kernels(hlo, kernel) >= 1, \
        [ln.strip()[:100] for ln in hlo.splitlines()
         if "tpu_custom_call" in ln]


def test_dense_decode_chunk_keeps_its_temporaries_for_v5e(one_chip,
                                                          monkeypatch):
    """The dense decode cells' whole 8-tick ``_decode_chunk``
    (qwen1.5-0.5b at published widths, 16 slots, 40-page tables over a
    641-page fp32 pool), compiled with its Pallas kernels for the
    described chip: its temporaries stay at the 4.44 GB they have held
    since the cells were added.  Adding the MoE counts to the scan's
    stacked outputs once tipped XLA's buffer assignment of the pools to
    12.5 GB and halved the cells' tokens/s on the chip; the counts ride
    the scan's carry instead."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import init_params
    from repro.serving.engine import _decode_chunk

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the kernels, not refs
    cfg = get_config("qwen1.5-0.5b")
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    b, ps, mp = 16, 16, 40
    pool = _sds((b * mp + 1, cfg.kv_heads, ps, cfg.head_dim_()), "float32",
                one_chip)
    caches = [{"k": pool, "v": pool} for _ in range(cfg.n_layers)]
    vec = lambda dt: _sds((b,), dt, one_chip)  # noqa: E731
    compiled = _decode_chunk.lower(
        params, caches, _sds((b, 1), "int32", one_chip), vec("int32"),
        _sds((b, mp), "int32", one_chip), _sds((b, 2), "uint32", one_chip),
        vec("float32"), vec("int32"), vec("float32"), vec("int32"),
        cfg=cfg, ticks=8, eos_id=None, sampled=False, guard=True).compile()
    assert _named_kernels(compiled.as_text(), "paged_attention_decode") >= 1
    assert compiled.memory_analysis().temp_size_in_bytes <= 4.5e9


def _computations(hlo: str) -> dict:
    """The compiled module's computations: name -> instruction lines."""
    out, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line.rstrip())
        if head:
            cur = out[head.group(1)] = []
        elif cur is not None:
            cur.append(line.strip())
    return out


def _reachable(comps: dict, roots) -> set:
    """``roots`` and every computation they call, however nested."""
    ref = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation|branch_computations)=\{?([^}]+?)\}?"
                     r"(?:, [a-z_]+=|$)")
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for group in ref.findall(line):
                todo += [n.strip().lstrip("%") for n in group.split(",")]
    return seen


def _pool_copies(hlo: str, pool_shape) -> tuple:
    """(inside a while body, elsewhere): the ``copy`` and ``copy-start``
    instructions that move a whole fp32 pool of ``pool_shape``."""
    comps = _computations(hlo)
    bodies = [m.group(1) for lines in comps.values() for line in lines
              for m in [re.search(r" while\(.*body=%([\w.\-]+)", line)] if m]
    loop = _reachable(comps, bodies)
    shape = "f32[" + ",".join(map(str, pool_shape)) + "]"
    copy = re.compile(r"^(?:ROOT )?%[\w.\-]+ = (.*?) copy(?:-start)?\(")
    counts = [0, 0]
    for name, lines in comps.items():
        for line in lines:
            m = copy.match(line)
            if m and shape in m.group(1):
                counts[name not in loop] += 1
    return tuple(counts)


@pytest.mark.parametrize("model", ["qwen", "mellum2"])
def test_decode_chunk_updates_its_pools_in_place_for_v5e(one_chip,
                                                         monkeypatch, model):
    """The cells' whole 8-tick ``_decode_chunk``, compiled with its Pallas
    kernels for the described chip, writes each tick's K/V through the
    aliased ``paged_kv_write`` and copies no pool inside its loop: an XLA
    scatter there held the pools in a layout the Mosaic walk does not
    take (a copy of every pool every tick), and an all-done ``lax.cond``
    arm holding the pools copied them in one arm.  qwen (16 slots, 40-page
    tables over a 641-page pool, dh 64) keeps its copies at the chunk's
    entry and exit, its pools' resident layout; mellum2 (16 layers,
    3073-page pool of K 4, dh 128, 192-page tables), whose pool is
    already in the kernels' layout, copies none anywhere."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import init_params
    from repro.serving.engine import _decode_chunk

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the kernels, not refs
    if model == "qwen":
        cfg, mp, n_pages = get_config("qwen1.5-0.5b"), 40, 641
    else:
        cfg = get_config("mellum2-12b-a2.5b").replace(
            n_layers=16, moe_experts_held=8, moe_expert_offset=0)
        mp, n_pages = MELLUM["mp"], MELLUM["pages"]
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    b, ps = 16, 16
    shape = (n_pages, cfg.kv_heads, ps, cfg.head_dim_())
    pool = _sds(shape, "float32", one_chip)
    caches = [{"k": pool, "v": pool} for _ in range(cfg.n_layers)]
    vec = lambda dt: _sds((b,), dt, one_chip)  # noqa: E731
    hlo = _decode_chunk.lower(
        params, caches, _sds((b, 1), "int32", one_chip), vec("int32"),
        _sds((b, mp), "int32", one_chip), _sds((b, 2), "uint32", one_chip),
        vec("float32"), vec("int32"), vec("float32"), vec("int32"),
        cfg=cfg, ticks=8, eos_id=None, sampled=False,
        guard=True).compile().as_text()
    assert _named_kernels(hlo, "paged_kv_write") == cfg.n_layers
    in_loop, elsewhere = _pool_copies(hlo, shape)
    assert in_loop == 0
    if model == "mellum2":
        assert elsewhere == 0
