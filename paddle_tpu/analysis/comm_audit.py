"""Collective wire-byte accounting over a traced (never executed) jaxpr.

The planner's `cost_model.estimate_layout_cost` prices each mesh axis's
collectives analytically (sp ring K/V hops, ep dispatch/combine
all-to-all, ...). This module closes the honesty loop from the other
side: walk the jaxpr of a REAL program (the ring-attention step, the
MoE layer) and total the payload bytes each collective primitive
actually moves — scan bodies multiplied by their trip count, shard_map
bodies counted at their per-device shapes. The cost-model honesty test
(tests/test_moe.py) asserts the analytic terms agree with this
trace-derived accounting within tolerance, so the planner's ranking
can't silently drift away from what the programs it ranks really do.

Wire-fraction convention: `all_to_all`/`all_gather`/`reduce_scatter`
contribute (n-1)/n of the operand bytes (each device keeps its own
shard), `ppermute` the full operand (every element moves one hop),
`psum`/`pmean` 2(n-1)/n (ring all-reduce). Axis sizes come from the
`axis_sizes` argument; unknown axes count at full payload.

Third honesty leg (`check_commbench_wire_bytes`): the mesh
observatory's MEASURED sweep records (telemetry/comm_obs) claim
wire_bytes through the same `_wire_bytes` convention — this check
rebuilds each measured sweep program, re-traces it, and requires the
record's claim to agree with this module's jaxpr-derived accounting
within the same 2x band the analytic-vs-traced legs use. Analytic
terms, traced programs, and measured records now all triangulate.
"""
import numpy as np

__all__ = ["check_commbench_wire_bytes", "collective_wire_bytes",
           "trace_collective_wire_bytes"]

# primitive name -> wire-fraction rule
_FULL = ("ppermute",)
_SHARD = ("all_to_all", "all_gather", "reduce_scatter")
_ALLREDUCE = ("psum",)   # pmean lowers to psum + divide
# under shard_map's varying-axes tracking (check_vma=True) a psum /
# all_gather whose result is replicated traces as the *_invariant
# primitive; the wire cost is the plain collective's
_CANON = {"psum_invariant": "psum", "all_gather_invariant": "all_gather"}


def _axis_size(eqn, axis_sizes):
    names = eqn.params.get("axis_name", eqn.params.get("axes"))
    if names is None:
        return None
    if not isinstance(names, (tuple, list)):
        names = (names,)
    n = 1
    known = False
    for a in names:
        if a in (axis_sizes or {}):
            n *= int(axis_sizes[a])
            known = True
    return n if known else None


def _operand_bytes(eqn):
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        total += int(np.prod(aval.shape or (1,))) * \
            np.dtype(aval.dtype).itemsize
    return total


def _wire_bytes(name, payload, n):
    if n is None or n <= 1:
        frac = 1.0
    elif name in _SHARD:
        frac = (n - 1) / n
    elif name in _ALLREDUCE:
        frac = 2.0 * (n - 1) / n
    else:
        frac = 1.0
    return payload * frac


def _walk(jaxpr, mult, axis_sizes, out):
    for eqn in jaxpr.eqns:
        name = _CANON.get(eqn.primitive.name, eqn.primitive.name)
        if name in _FULL + _SHARD + _ALLREDUCE:
            entry = out.setdefault(name, {"calls": 0, "bytes": 0.0})
            entry["calls"] += mult
            entry["bytes"] += mult * _wire_bytes(
                name, _operand_bytes(eqn), _axis_size(eqn, axis_sizes))
        inner_mult = mult
        if name == "scan":
            inner_mult = mult * int(eqn.params.get("length", 1))
        for sub in _sub_jaxprs(eqn):
            _walk(sub, inner_mult, axis_sizes, out)
    return out


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        yield from _jaxprs_in(v)


def _jaxprs_in(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_in(x)


def collective_wire_bytes(closed_jaxpr, axis_sizes=None):
    """{primitive: {calls, bytes}} over a ClosedJaxpr (recursing into
    scan/cond/pjit/shard_map bodies; scan bodies weighted by length)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return _walk(jaxpr, 1, axis_sizes or {}, {})


def trace_collective_wire_bytes(fn, *args, axis_sizes=None):
    """Trace `fn(*args)` with make_jaxpr (no execution) and account its
    collectives. args may be arrays or ShapeDtypeStructs."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    return collective_wire_bytes(closed, axis_sizes=axis_sizes)


# primitive names each sweep op's program may legitimately lower to
# (pmean -> psum + divide is the existing precedent; reduce_scatter is
# lax.psum_scatter's primitive of the same name)
_OP_PRIMS = {
    "psum": ("psum",),
    "all_gather": ("all_gather",),
    "reduce_scatter": ("reduce_scatter",),
    "all_to_all": ("all_to_all",),
    "ppermute": ("ppermute",),
}


def check_commbench_wire_bytes(records, mesh=None, band=2.0):
    """Third leg of the comm honesty loop: measured commbench records'
    claimed wire_bytes vs this module's jaxpr-derived accounting of the
    SAME sweep program, rebuilt and re-traced (never executed) on the
    live mesh. Returns problem strings ([] == honest): a claim off by
    more than `band`x either way, a rebuilt program whose jaxpr shows
    no collective, or a record naming an axis the mesh lacks. Records
    that are not measurement rows (event=db_update echoes, null
    timings) or that claim no wire_bytes are skipped — there is
    nothing to cross-check. Runs inside `commlab --selfcheck`, so CI
    enforces that the harness and the auditor cannot drift apart."""
    import jax
    from ..distributed import env
    from ..telemetry import comm_obs

    mesh = mesh if mesh is not None else env.current_mesh()
    if mesh is None:
        return ["check_commbench_wire_bytes: no mesh — pass mesh= or "
                "env.build_mesh(...) first"]
    problems = []
    axis_sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    for i, rec in enumerate(records or ()):
        if not isinstance(rec, dict) or rec.get("kind") != "commbench":
            continue
        if rec.get("event") not in (None, "measure"):
            continue
        claimed = rec.get("wire_bytes")
        op, axis = rec.get("op"), rec.get("axis")
        if not claimed or op not in _OP_PRIMS:
            continue
        if axis not in axis_sizes:
            problems.append(
                f"record {i} ({op}): axis {axis!r} not on the live mesh "
                f"(axes: {sorted(axis_sizes)})")
            continue
        fn, sds, _spec, _actual = comm_obs.sweep_program(
            op, axis, mesh, rec.get("payload_bytes", 0))
        acct = trace_collective_wire_bytes(
            fn, jax.ShapeDtypeStruct(sds.shape, sds.dtype),
            axis_sizes=axis_sizes)
        analytic = sum(e["bytes"] for name, e in acct.items()
                       if name in _OP_PRIMS[op])
        if analytic <= 0:
            problems.append(
                f"record {i} ({op} over {axis!r}): rebuilt sweep program "
                "traces to NO collective bytes — the harness and the "
                "auditor disagree about what the sweep runs")
            continue
        ratio = float(claimed) / analytic
        if not (1.0 / band) <= ratio <= band:
            problems.append(
                f"record {i} ({op} over {axis!r}, "
                f"{rec.get('payload_bytes')} B): claimed wire_bytes "
                f"{float(claimed):.0f} vs jaxpr-derived {analytic:.0f} "
                f"({ratio:.2f}x, band {band:.1f}x) — the measurement's "
                "byte claim does not describe the program it measured")
    return problems
