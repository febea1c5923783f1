"""Runtime enforcement for the static analyzer's two dynamic claims
(DESIGN.md section 14): *steady-state decode performs zero recompiles*
and *at most one device->host transfer boundary per chunk*.

Three cooperating pieces:

* **Compile tracking** — `CompileTracker` snapshots per-function jit
  cache sizes (`fn._cache_size()`) plus a process-wide compile-event
  counter fed by `jax.monitoring`.  Cache sizes are exact per tracked
  function; the event counter is a tripwire for compiles anywhere else.
* **Sync regions** — `sync_region(tag)` declares an *intentional*
  blocking host round-trip (the engine wraps its one-per-chunk
  `jax.device_get` in one).  Regions are counted per tag; "<=1 transfer
  per chunk" means exactly one region entered per decode chunk.
* **Stray-pull interception** — `no_host_sync()` patches the concrete
  jax Array host-materialisation hooks (`__array__`, `item`,
  `__float__`, ...) *and* the module entry points `np.asarray`,
  `np.array`, `jax.device_get`, so any pull *outside* a declared region
  raises `HostSyncError`.  The module-level patches matter: on CPU,
  `ArrayImpl` exposes the C buffer protocol, so `np.asarray` grabs a
  zero-copy view without ever calling the Python `__array__` hook — the
  only Python-visible choke point is the caller's module attribute.
  `jax.transfer_guard_device_to_host("disallow")` is layered on as
  well; the transfer guard only enforces on accelerator backends — on
  CPU the host "transfer" is zero-copy and the guard never fires, which
  is exactly why the patch-based meter exists.

All counters are process-global (jit caches are module-global too); the
engine keeps its own per-instance region counts for `analysis_stats()`.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


class HostSyncError(RuntimeError):
    """A device->host pull happened outside any declared sync_region."""


# ---------------------------------------------------------------------------
# Compile-event counter (process-wide tripwire)
# ---------------------------------------------------------------------------

_compile_events = 0
_listener_installed = False


def _on_event(event: str, **kwargs: Any) -> None:
    global _compile_events
    if "compile" in event:
        _compile_events += 1


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    jax.monitoring.register_event_listener(_on_event)
    _listener_installed = True


def compile_events() -> int:
    """Process-wide count of compile-related monitoring events so far."""
    _install_listener()
    return _compile_events


def cache_size(fn: Any) -> int:
    """Size of a jitted function's compile cache (-1 if unknown)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


class CompileTracker:
    """Snapshot/diff jit cache sizes for a set of tracked functions."""

    def __init__(self, **fns: Any) -> None:
        self._fns = dict(fns)
        _install_listener()

    def snapshot(self) -> Dict[str, Any]:
        return {
            "events": compile_events(),
            "caches": {name: cache_size(fn) for name, fn in self._fns.items()},
        }

    @staticmethod
    def new_compiles(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, int]:
        """Per-function cache growth between two snapshots (+ event delta)."""
        out = {
            name: after["caches"].get(name, -1) - size
            for name, size in before["caches"].items()
        }
        out["_events"] = after["events"] - before["events"]
        return out


# ---------------------------------------------------------------------------
# Sync regions + stray-pull interception
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_region_stack: List[str] = []
_region_counts: Dict[str, int] = {}
_pull_counts: Dict[str, int] = {}
_strict_depth = 0
_meter_depth = 0
_saved_attrs: Dict[str, Any] = {}
_saved_mod_attrs: Dict[str, Any] = {}
_in_pull = threading.local()

_PULL_HOOKS = ("__array__", "item", "__float__", "__int__", "__bool__", "__index__", "tolist")
# caller-side entry points: on CPU the buffer protocol serves np.asarray
# a zero-copy view with no Python hook in the path, so the module
# attribute is the only interceptable choke point.
_MODULE_FUNCS = (("np.asarray", np, "asarray"), ("np.array", np, "array"),
                 ("jax.device_get", jax, "device_get"))


_array_cls_cache: Optional[type] = None


def _array_cls() -> type:
    # cached: computing it runs jnp.zeros, which itself routes through
    # the patched np.asarray while the meter is active.
    global _array_cls_cache
    if _array_cls_cache is None:
        _array_cls_cache = type(jnp.zeros((), jnp.int32))
    return _array_cls_cache


def _record_pull(hook: str) -> None:
    tag = _region_stack[-1] if _region_stack else None
    if tag is None and _strict_depth > 0:
        raise HostSyncError(
            f"device->host pull via `{hook}` outside any sync_region while "
            f"no_host_sync() is active — wrap the pull in "
            f"repro.analysis.runtime.sync_region(tag) or remove it"
        )
    key = tag if tag is not None else "<untagged>"
    _pull_counts[key] = _pull_counts.get(key, 0) + 1


def _has_device_leaf(args: Any, kwargs: Any) -> bool:
    cls = _array_cls()
    try:
        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:  # exotic containers — be quiet rather than wrong
        return False
    return any(isinstance(leaf, cls) for leaf in leaves)


def _activate_meter() -> None:
    global _meter_depth
    with _lock:
        _meter_depth += 1
        if _meter_depth > 1:
            return
        cls = _array_cls()
        for name in _PULL_HOOKS:
            orig = getattr(cls, name, None)
            if orig is None:
                continue
            _saved_attrs[name] = orig

            def _wrap(orig: Callable, hook: str) -> Callable:
                @functools.wraps(orig)
                def wrapper(self, *args: Any, **kwargs: Any):
                    if not getattr(_in_pull, "depth", 0):
                        _record_pull(hook)
                    return orig(self, *args, **kwargs)

                return wrapper

            setattr(cls, name, _wrap(orig, name))
        for label, mod, attr in _MODULE_FUNCS:
            orig = getattr(mod, attr)
            _saved_mod_attrs[label] = orig

            def _wrap_mod(orig: Callable, hook: str) -> Callable:
                def wrapper(*args: Any, **kwargs: Any):
                    # record once per outermost pull: device_get calls
                    # np.asarray internally, don't double-count.
                    nested = getattr(_in_pull, "depth", 0)
                    if not nested and _has_device_leaf(args, kwargs):
                        _record_pull(hook)
                    _in_pull.depth = nested + 1
                    try:
                        return orig(*args, **kwargs)
                    finally:
                        _in_pull.depth = nested

                return wrapper

            setattr(mod, attr, _wrap_mod(orig, label))


def _deactivate_meter() -> None:
    global _meter_depth
    with _lock:
        _meter_depth -= 1
        if _meter_depth > 0:
            return
        cls = _array_cls()
        for name, orig in _saved_attrs.items():
            setattr(cls, name, orig)
        _saved_attrs.clear()
        for label, mod, attr in _MODULE_FUNCS:
            if label in _saved_mod_attrs:
                setattr(mod, attr, _saved_mod_attrs.pop(label))


@contextlib.contextmanager
def sync_region(tag: str) -> Iterator[None]:
    """Declare one intentional blocking host round-trip.

    Counted per tag; inside the region host pulls are allowed (and
    counted when a meter is active).  Layered transfer-guard `allow`
    covers accelerator backends where the guard actually enforces.
    The region is a ``repro.sync.<tag>`` span on the profiler's trace.
    """
    _region_counts[tag] = _region_counts.get(tag, 0) + 1
    _region_stack.append(tag)
    try:
        with (jax.profiler.TraceAnnotation(f"repro.sync.{tag}"),
              jax.transfer_guard_device_to_host("allow")):
            yield
    finally:
        _region_stack.pop()


@contextlib.contextmanager
def no_host_sync(strict: bool = True) -> Iterator[None]:
    """Forbid device->host pulls outside declared sync_regions.

    `strict=True` raises `HostSyncError` on the first stray pull;
    `strict=False` only counts them (under the "<untagged>" tag).
    """
    global _strict_depth
    _activate_meter()
    if strict:
        _strict_depth += 1
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        if strict:
            _strict_depth -= 1
        _deactivate_meter()


@contextlib.contextmanager
def measure_pulls() -> Iterator[Dict[str, int]]:
    """Count host pulls per region tag without forbidding anything."""
    start = dict(_pull_counts)
    _activate_meter()
    try:
        delta: Dict[str, int] = {}
        yield delta
    finally:
        _deactivate_meter()
        for k, v in _pull_counts.items():
            d = v - start.get(k, 0)
            if d:
                delta[k] = d


def region_counts() -> Dict[str, int]:
    return dict(_region_counts)


def reset_counters() -> None:
    _region_counts.clear()
    _pull_counts.clear()
