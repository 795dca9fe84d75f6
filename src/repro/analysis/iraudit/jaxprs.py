"""Shared jaxpr-walking utilities for the iraudit passes.

Everything here is *structural*: counts and byte sizes read straight off
the (closed) jaxpr, never multiplied by loop trip counts — that keeps the
numbers exact and jax-version-stable, which is what golden snapshots and
exact budget gates need.  Trip-count-aware costs live in the HLO pass
(``analysis/hlo_cost.py``).
"""
from __future__ import annotations

from collections import Counter
from typing import Iterator

import numpy as np
from jax.extend import core as jcore


def _sub_jaxprs(eqn) -> Iterator[jcore.Jaxpr]:
    """Yield every Jaxpr nested in an eqn's params (scan/while/cond/pjit
    bodies, pallas_call kernels, custom_*_call — anything jaxpr-valued)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def iter_eqns(jaxpr: jcore.Jaxpr, *, depth: int = 0):
    """Depth-first walk over every eqn, recursing into sub-jaxprs.

    Yields ``(eqn, depth)``; depth 0 is the entry jaxpr itself, so a
    caller can restrict a check to the top level when sub-graphs (e.g.
    Pallas kernel bodies) play by different rules.
    """
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, depth=depth + 1)


def op_census(closed: jcore.ClosedJaxpr) -> dict:
    """Structural primitive census: ``{primitive_name: count}`` over the
    whole jaxpr including nested bodies (each body counted once, not per
    trip — golden-snapshot stable)."""
    c: Counter = Counter()
    for eqn, _ in iter_eqns(closed.jaxpr):
        c[eqn.primitive.name] += 1
    return dict(sorted(c.items()))


def _itemsize(dtype) -> int:
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG keys): key<fry> carries 2 x uint32
        return getattr(dtype, "itemsize", 8)


def _aval_bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None or shape is None:
        return 0
    n = 1
    for d in shape:
        if not isinstance(d, int):   # symbolic dims: not used on these paths
            return 0
        n *= d
    return n * _itemsize(dtype)


def _var_bytes(v) -> int:
    return 0 if isinstance(v, jcore.Literal) else _aval_bytes(v.aval)


def const_census(closed: jcore.ClosedJaxpr) -> tuple[int, int, list]:
    """Closure-captured constants of the traced entrypoint.

    Returns ``(count, total_bytes, rows)`` with one ``(dtype, shape,
    bytes)`` row per const, largest first.  Every const here is a buffer
    jit re-uploads alongside the arguments — the dynamic counterpart of
    tapaslint TL008.
    """
    rows = []
    for c in closed.consts:
        arr = np.asarray(c)
        rows.append((str(arr.dtype), tuple(arr.shape),
                     int(arr.size * arr.dtype.itemsize)))
    rows.sort(key=lambda r: (-r[2], r[0], r[1]))
    return len(rows), sum(r[2] for r in rows), rows


def f32_out_bytes(closed: jcore.ClosedJaxpr) -> int:
    """Structural bytes of every f32/f64 eqn output in the graph (nested
    bodies included, counted once).  A creep detector: bf16-configured
    graphs hold a small, deliberate f32 surface (softmax scores, sampling
    distributions, kernel accumulators) and this pins its size."""
    wide = (np.dtype(np.float32), np.dtype(np.float64))
    total = 0
    for eqn, _ in iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            try:
                is_wide = dt is not None and np.dtype(dt) in wide
            except TypeError:      # extended dtypes (PRNG keys)
                is_wide = False
            if is_wide:
                total += _var_bytes(v)
    return total


def peak_live_bytes(closed: jcore.ClosedJaxpr) -> int:
    """Deterministic peak-live estimate from jaxpr liveness.

    Linear scan of each jaxpr's eqns tracking live defined values (args +
    consts + not-yet-dead outputs); at an eqn with a nested body the
    body's own peak is stacked on top of the caller's live set.  This is
    an upper-bound proxy (no aliasing/donation credit, buffers die at
    last textual use), but it is exact arithmetic over the IR — stable
    enough to gate exactly, unlike XLA's allocator-dependent numbers.
    """
    def walk(jaxpr: jcore.Jaxpr) -> int:
        last_use: dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, jcore.Literal):
                    last_use[v] = i
        for v in jaxpr.outvars:
            if not isinstance(v, jcore.Literal):
                last_use[v] = len(jaxpr.eqns)
        live = {v: _var_bytes(v)
                for v in (*jaxpr.invars, *jaxpr.constvars)}
        cur = sum(live.values())
        peak = cur
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.outvars:
                if v not in live:
                    live[v] = _var_bytes(v)
                    cur += live[v]
            inner = max((walk(sub) for sub in _sub_jaxprs(eqn)), default=0)
            peak = max(peak, cur + inner)
            for v in list(live):
                if last_use.get(v, -1) <= i:
                    cur -= live.pop(v)
        return peak

    return walk(closed.jaxpr)
