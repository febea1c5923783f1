"""pallas-constraints rule: structural checks on `pl.pallas_call` sites.

Three checks per call site:

* **index-map arity** — every `BlockSpec` index map must take exactly
  `len(grid) + num_scalar_prefetch` parameters, and (when both are
  literal) return as many coordinates as the block shape has dims.
  Mismatches surface as shape errors deep inside lowering; here they
  are one line.
* **traced captures** — an index map runs at trace/lowering time; a
  lambda that closes over a name whose *staticness is not locally
  provable* (not a constant, `.shape` access, int-annotated/defaulted
  parameter, or arithmetic over those) risks capturing a tracer.  The
  prover is deliberately conservative: `min(...)`-style calls are
  unproven even when static by construction — suppress with a note.
* **interpret path** — every `pallas_call` must thread an `interpret=`
  kwarg and the enclosing function must expose an `interpret`
  parameter, so kernels stay debuggable/testable off-accelerator
  (the repo's CPU CI runs every kernel in interpret mode).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..lint import Finding, FunctionInfo, ProjectIndex, Rule, dotted_name
from . import register

_PALLAS_CALL_NAMES = {"pallas_call", "pl.pallas_call"}
_GRID_SPEC_NAMES = {"PrefetchScalarGridSpec", "GridSpec"}

# Builtins/globals an index map may reference freely.
_SAFE_GLOBALS = {
    "len", "min", "max", "abs", "int", "sum", "range", "tuple", "divmod",
    "jnp", "jax", "pl", "lax", "np", "functools", "math",
}


def _is_pallas_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return name is not None and name.split(".")[-1] == "pallas_call"


def _static_env(fn: ast.AST) -> Dict[str, List[ast.AST]]:
    """name -> *every* defining expression, for local staticness proofs.

    A name is provably static only if all of its bindings are — no flow
    analysis, so one unproven reassignment poisons the name.
    """
    env: Dict[str, List[ast.AST]] = {}
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        args = fn.args
        defaults = list(args.defaults)
        pos = list(args.args)
        # align defaults to the tail of positional params
        for i, a in enumerate(pos):
            d_idx = i - (len(pos) - len(defaults))
            default = defaults[d_idx] if d_idx >= 0 else None
            is_int_ann = (
                isinstance(a.annotation, ast.Name) and a.annotation.id in ("int", "bool")
            )
            if isinstance(default, ast.Constant) and isinstance(default.value, (int, bool)):
                env[a.arg] = [default]
            elif is_int_ann:
                env[a.arg] = [ast.Constant(value=0)]  # marker: int-typed param
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if isinstance(d, ast.Constant) and isinstance(d.value, (int, bool)):
                env[a.arg] = [d]
            elif isinstance(a.annotation, ast.Name) and a.annotation.id in ("int", "bool"):
                env[a.arg] = [ast.Constant(value=0)]
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            env.setdefault(node.targets[0].id, []).append(node.value)
    return env


def provably_static(expr: ast.AST, env: Dict[str, List[ast.AST]], _seen: Optional[Set[str]] = None) -> bool:
    """Conservative proof that `expr` is a Python value at trace time."""
    seen = _seen or set()
    if isinstance(expr, ast.Constant):
        return True
    if isinstance(expr, ast.Name):
        if expr.id in seen:
            return False
        bindings = env.get(expr.id)
        if not bindings:
            return False
        return all(provably_static(b, env, seen | {expr.id}) for b in bindings)
    if isinstance(expr, ast.Attribute):
        # x.shape / x.ndim / x.size are static under trace regardless of x
        return expr.attr in ("shape", "ndim", "size", "dtype")
    if isinstance(expr, ast.Subscript):
        return provably_static(expr.value, env, seen)
    if isinstance(expr, ast.BinOp):
        return provably_static(expr.left, env, seen) and provably_static(expr.right, env, seen)
    if isinstance(expr, ast.UnaryOp):
        return provably_static(expr.operand, env, seen)
    if isinstance(expr, ast.Call):
        # len(...) of anything is static under trace; everything else unproven
        return dotted_name(expr.func) == "len"
    if isinstance(expr, (ast.Tuple, ast.List)):
        return all(provably_static(e, env, seen) for e in expr.elts)
    return False


def _map_params(fn) -> List[str]:
    return [a.arg for a in fn.args.args]


def _map_body(fn) -> ast.AST:
    if isinstance(fn, ast.Lambda):
        return fn.body
    # nested `def pool_map(...)`: use the returned expression if single-return
    rets = [n.value for n in ast.walk(fn) if isinstance(n, ast.Return) and n.value is not None]
    return rets[0] if len(rets) == 1 else fn


def _index_map_free_names(fn) -> Set[str]:
    bound = set(_map_params(fn)) | {a.arg for a in fn.args.kwonlyargs}
    body = fn.body if isinstance(fn, ast.Lambda) else fn
    nodes = list(ast.walk(body if isinstance(body, ast.AST) else fn))
    # names assigned inside the map body are its locals, not captures
    for node in nodes:
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
    free: Set[str] = set()
    for node in nodes:
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id not in bound and node.id not in _SAFE_GLOBALS:
                free.add(node.id)
    return free


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _block_specs(node: ast.AST) -> List[ast.Call]:
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            n = dotted_name(sub.func)
            if n is not None and n.split(".")[-1] == "BlockSpec":
                out.append(sub)
    return out


def _spec_parts(spec: ast.Call, local_defs: Dict[str, ast.FunctionDef]) -> Tuple[Optional[ast.AST], Optional[ast.AST]]:
    """(index_map callable, block_shape expr) from a BlockSpec call.

    Either argument order; the index map may be an inline lambda, a
    Name referring to a nested `def` in the enclosing function, or
    `functools.partial` of such a def binding its keyword-only
    parameters (one map per page slot, say).
    """
    fn: Optional[ast.AST] = None
    shape: Optional[ast.AST] = None
    candidates = list(spec.args) + [kw.value for kw in spec.keywords]
    for a in candidates:
        if (isinstance(a, ast.Call) and a.args and fn is None
                and (dotted_name(a.func) or "").split(".")[-1] == "partial"):
            a = a.args[0]
        if isinstance(a, ast.Lambda) and fn is None:
            fn = a
        elif isinstance(a, ast.Name) and a.id in local_defs and fn is None:
            fn = local_defs[a.id]
        elif shape is None:
            shape = a
    return fn, shape


def _grid_rank_and_prefetch(call: ast.Call, fn_env: Dict[str, List[ast.AST]]) -> Tuple[Optional[int], int]:
    """Grid rank + num_scalar_prefetch for a pallas_call, following one
    level of local name indirection for `grid_spec=name` bindings."""
    grid = _kw(call, "grid")
    prefetch = 0
    spec = _kw(call, "grid_spec")
    if spec is not None:
        if isinstance(spec, ast.Name):
            bindings = fn_env.get(spec.id)
            spec = bindings[-1] if bindings else None
        if isinstance(spec, ast.Call) and dotted_name(spec.func) is not None and \
                dotted_name(spec.func).split(".")[-1] in _GRID_SPEC_NAMES:
            grid = _kw(spec, "grid") or (spec.args[0] if spec.args else None)
            pf = _kw(spec, "num_scalar_prefetch")
            if isinstance(pf, ast.Constant) and isinstance(pf.value, int):
                prefetch = pf.value
    if isinstance(grid, (ast.Tuple, ast.List)):
        return len(grid.elts), prefetch
    if isinstance(grid, ast.Name):
        bindings = fn_env.get(grid.id)
        if bindings and isinstance(bindings[-1], (ast.Tuple, ast.List)):
            return len(bindings[-1].elts), prefetch
    return None, prefetch


@register
class PallasConstraintsRule(Rule):
    name = "pallas-constraints"
    doc = (
        "BlockSpec index-map arity vs grid, index maps capturing names "
        "not provably static, and pallas_call sites without an "
        "interpret-mode path."
    )

    def check(self, index: ProjectIndex) -> Iterable[Finding]:
        for mod in index.modules:
            mod_env: Dict[str, List[ast.AST]] = {}
            for stmt in mod.tree.body:
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name):
                    mod_env[stmt.targets[0].id] = [stmt.value]
            for fi in mod.functions:
                if not isinstance(fi.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                calls = [
                    n for n in ast.walk(fi.node)
                    if isinstance(n, ast.Call) and _is_pallas_call(n)
                ]
                if not calls:
                    continue
                env = {**mod_env, **_static_env(fi.node)}
                fn_params = {a.arg for a in fi.node.args.args} | {
                    a.arg for a in fi.node.args.kwonlyargs
                }
                local_defs = {
                    n.name: n for n in ast.walk(fi.node)
                    if isinstance(n, ast.FunctionDef) and n is not fi.node
                }
                for call in calls:
                    yield from self._check_call(mod, fi, call, env, fn_params, local_defs)

    def _check_call(self, mod, fi, call: ast.Call, env, fn_params, local_defs) -> Iterable[Finding]:
        # interpret path
        if _kw(call, "interpret") is None or "interpret" not in fn_params:
            yield Finding(
                rule=self.name, path=mod.path, line=call.lineno, col=call.col_offset,
                symbol=fi.qualname,
                message="pallas_call without an `interpret=` kwarg threaded from an "
                "`interpret` parameter — kernel has no off-accelerator path",
            )
        rank, prefetch = _grid_rank_and_prefetch(call, env)
        # BlockSpecs may sit inside a `grid_spec = PrefetchScalarGridSpec(...)`
        # local binding rather than inline in the pallas_call
        spec_sources: List[ast.AST] = [call]
        gs = _kw(call, "grid_spec")
        if isinstance(gs, ast.Name):
            bindings = env.get(gs.id)
            if bindings:
                spec_sources.append(bindings[-1])
        # ... or in a local list of specs spread into it (`*pages`)
        for src in list(spec_sources):
            for n in ast.walk(src):
                if isinstance(n, ast.Starred) and isinstance(n.value, ast.Name) \
                        and env.get(n.value.id) \
                        and env[n.value.id][-1] not in spec_sources:
                    spec_sources.append(env[n.value.id][-1])
        for spec in [s for src in spec_sources for s in _block_specs(src)]:
            imap, shape = _spec_parts(spec, local_defs)
            if imap is None:
                continue
            map_name = imap.name if isinstance(imap, ast.FunctionDef) else "<lambda>"
            n_params = len(_map_params(imap))
            if rank is not None and n_params != rank + prefetch:
                yield Finding(
                    rule=self.name, path=mod.path, line=spec.lineno, col=spec.col_offset,
                    symbol=fi.qualname,
                    message=f"index_map `{map_name}` takes {n_params} args but grid rank "
                    f"{rank} + {prefetch} scalar-prefetch refs = {rank + prefetch} expected",
                )
            body = _map_body(imap)
            if isinstance(shape, (ast.Tuple, ast.List)) and isinstance(body, (ast.Tuple, ast.List)):
                if len(body.elts) != len(shape.elts):
                    yield Finding(
                        rule=self.name, path=mod.path, line=spec.lineno, col=spec.col_offset,
                        symbol=fi.qualname,
                        message=f"index_map `{map_name}` returns {len(body.elts)} coords "
                        f"but block_shape has {len(shape.elts)} dims",
                    )
            for name in sorted(_index_map_free_names(imap)):
                if not provably_static(ast.Name(id=name, ctx=ast.Load()), env):
                    yield Finding(
                        rule=self.name, path=mod.path, line=spec.lineno, col=spec.col_offset,
                        symbol=fi.qualname,
                        message=f"index_map `{map_name}` captures `{name}` whose staticness "
                        f"is not locally provable — a traced capture would lower into "
                        f"the index computation",
                    )
