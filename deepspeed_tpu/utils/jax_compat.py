"""Thin helpers over the jax API surface the repo is written against
(jax 0.9: ``jax.shard_map`` with ``axis_names``/``check_vma``), plus
normalisers for what ``Compiled.cost_analysis()`` /
``memory_analysis()`` return on the backends the repo runs on.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with the repo's defaults (``check_vma`` off;
    ``axis_names=None`` means every mesh axis is manual)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def normalize_cost_analysis(cost) -> dict:
    """One dict shape for ``Compiled.cost_analysis()`` across backends.
    The raw return is a dict, a LIST-of-one-dict on some backends, and
    ``None``/``[]``/``{}`` on backends (CPU notably) that expose no
    cost model for a given executable. Callers always get a plain dict with float values —
    possibly empty, never None — so ``.get("flops", 0.0)`` is safe
    everywhere."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not cost:
        return {}
    try:
        return {str(k): float(v) for k, v in dict(cost).items()
                if isinstance(v, (int, float))}
    except Exception:
        return {}


def normalize_memory_analysis(mem) -> dict:
    """``Compiled.memory_analysis()`` -> plain byte-count dict
    ``{argument, output, temp, alias, generated_code, peak}``.

    The raw return is a ``CompiledMemoryStats`` struct on most
    backends, a raw dict on some plugin backends, and ``None`` where
    the runtime exposes nothing (older CPU PJRT). ``peak`` prefers the
    backend's own figure when one exists
    (``peak_memory_in_bytes``/``peak_bytes``); otherwise it is the
    argument+output+temp sum — an upper bound on live HBM for one
    execution (aliased/donated bytes are double counted by the sum, so
    the true peak is never above it)."""
    if mem is None:
        return {}
    fields = {"argument": "argument_size_in_bytes",
              "output": "output_size_in_bytes",
              "temp": "temp_size_in_bytes",
              "alias": "alias_size_in_bytes",
              "generated_code": "generated_code_size_in_bytes"}
    out: dict = {}
    getter = (mem.get if isinstance(mem, dict)
              else lambda k, d=0: getattr(mem, k, d))
    try:
        for name, attr in fields.items():
            v = getter(attr, 0)
            if isinstance(v, (int, float)):
                out[name] = int(v)
        peak = 0
        for attr in ("peak_memory_in_bytes", "peak_bytes_in_use",
                     "peak_bytes"):
            v = getter(attr, 0)
            if isinstance(v, (int, float)) and v > 0:
                peak = int(v)
                break
        if peak <= 0:
            peak = (out.get("argument", 0) + out.get("output", 0)
                    + out.get("temp", 0))
        out["peak"] = peak
    except Exception:
        return {}
    return out


def supports_pinned_host() -> bool:
    """Whether the backend exposes a ``pinned_host`` memory tier. The
    single source of truth for offload placement decisions and the
    placement asserts in tests. A probe failure propagates: a backend
    that cannot list its memories is a fault to surface, not a reason
    to keep "offloaded" state in HBM."""
    return any(m.kind == "pinned_host"
               for m in jax.devices()[0].addressable_memories())
