"""Static per-primitive cost model over jaxprs: FLOPs + HBM bytes.

The hardware-independent half of the PERF story (ISSUE 7): the
PROGRAM is fully known at trace time — so this module walks a
ClosedJaxpr and prices every equation with a deterministic analytic
model. The absolute numbers are coarse (see the honesty notes below);
what the auditor gates on is their STABILITY: the same config must
price to the identical integer on every trace, so any drift in the
committed `audit.baseline.json` is a real program change someone must
look at — the static stand-in for a bench regression gate.

Model (deliberately simple, deliberately documented):

  * FLOPs — `dot_general` and `conv_general_dilated` get the exact
    2·M·N·K count from their dimension numbers; `sort`/`top_k` are
    priced as comparison networks (n·ceil(log2 n), n·ceil(log2 k));
    reductions cost their operand size; everything else costs its
    output size (one op per output element — transcendentals are
    undercounted by a small constant factor, uniformly, which cancels
    in a regression diff).
  * HBM bytes — every equation is priced as if un-fused: operand bytes
    in + result bytes out. Real XLA fuses elementwise chains, so this
    is an UPPER BOUND on traffic, not a prediction — but a new
    intermediate buffer shows up in it immediately, which is the
    regression class (an accidental [D]-materialization) the gate
    exists to catch.
  * Containers — `pjit`/`closed_call`/`remat`/`custom_*` recurse at
    cost ×1; `scan` multiplies its body by the trip count; `cond`
    prices the most expensive branch; `while` prices ONE iteration
    (trip count is dynamic — flagged in the report via `dynamic_loops`
    so a reader knows the total is a per-iteration figure there);
    `pallas_call` multiplies its kernel body by the grid size;
    `shard_map` prices the PER-SHARD program (wall-clock view: shards
    run in parallel).

Deliberately dependency-light: operates on jaxpr objects by duck
typing (`.eqns`, `.jaxpr`, avals with `.shape`/`.dtype`), imports
nothing from jax — so it loads anywhere and survives jax-internal
module moves.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, List, Optional, Tuple

# primitives priced as pure data movement (FLOPs 0): layout, slicing,
# indexing, conversion-free reshapes
_DATA_MOVEMENT = frozenset({
    "reshape", "broadcast_in_dim", "squeeze", "transpose", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "gather", "scatter", "rev", "copy", "convert_element_type",
    "bitcast_convert_type", "device_put", "iota", "roll",
    "random_wrap", "random_unwrap", "stop_gradient", "split",
    "program_id", "get", "swap",
    # the varying-axes type casts of shard_map's check_vma (jax 0.9.0
    # also wraps literals in them): no arithmetic
    "pvary", "pcast",
})

# reductions: one op per OPERAND element
_REDUCERS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin", "reduce_precision",
    "psum", "pmax", "pmin", "all_gather", "all_gather_invariant",
    "reduce_scatter",
})

# container primitives whose cost is their inner jaxpr's, with a
# multiplier; the eqn itself moves no bytes beyond what the body does
_CONTAINERS = frozenset({
    "pjit", "closed_call", "core_call", "xla_call", "remat", "remat2",
    "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "scan", "while",
    "cond", "shard_map", "pallas_call", "custom_partitioning",
})


class Cost:
    """Mutable accumulator: total flops/bytes + per-primitive rollup."""

    def __init__(self):
        self.flops = 0
        self.hbm_bytes = 0
        self.eqns = 0
        self.dynamic_loops = 0
        self.by_primitive: Dict[str, Dict[str, int]] = {}

    def add(self, prim: str, flops: int, hbm_bytes: int,
            mult: int = 1) -> None:
        flops, hbm_bytes = int(flops) * mult, int(hbm_bytes) * mult
        self.flops += flops
        self.hbm_bytes += hbm_bytes
        self.eqns += 1
        row = self.by_primitive.setdefault(
            prim, {"count": 0, "flops": 0, "hbm_bytes": 0})
        row["count"] += 1
        row["flops"] += flops
        row["hbm_bytes"] += hbm_bytes

    def merge(self, other: "Cost", mult: int = 1) -> None:
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        self.eqns += other.eqns
        self.dynamic_loops += other.dynamic_loops
        for prim, row in other.by_primitive.items():
            mine = self.by_primitive.setdefault(
                prim, {"count": 0, "flops": 0, "hbm_bytes": 0})
            mine["count"] += row["count"]
            mine["flops"] += row["flops"] * mult
            mine["hbm_bytes"] += row["hbm_bytes"] * mult

    def as_dict(self, top: int = 8) -> dict:
        """Canonical JSON-able report; `by_primitive` keeps the `top`
        most expensive primitives by FLOPs (ties broken by name so the
        report is bit-stable), plus an `other` rollup."""
        rows = sorted(self.by_primitive.items(),
                      key=lambda kv: (-kv[1]["flops"],
                                      -kv[1]["hbm_bytes"], kv[0]))
        head = {k: dict(v) for k, v in rows[:top]}
        tail = rows[top:]
        if tail:
            head["other"] = {
                "count": sum(v["count"] for _, v in tail),
                "flops": sum(v["flops"] for _, v in tail),
                "hbm_bytes": sum(v["hbm_bytes"] for _, v in tail),
            }
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "eqns": self.eqns,
            "dynamic_loops": self.dynamic_loops,
            "by_primitive": head,
        }


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return _numel(shape) * int(getattr(dtype, "itemsize", 4))


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _out_numel(eqn) -> int:
    return sum(_numel(getattr(v.aval, "shape", ()))
               for v in eqn.outvars)


def _operand_avals(eqn):
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "shape", None) is not None:
            yield aval


def _eqn_bytes(eqn) -> int:
    return (sum(aval_bytes(a) for a in _operand_avals(eqn))
            + sum(aval_bytes(v.aval) for v in eqn.outvars))


def _dot_flops(eqn) -> int:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs, rhs = [a.shape for a in _operand_avals(eqn)][:2]
    k = _numel([lhs[i] for i in lc])
    b = _numel([lhs[i] for i in lb])
    m = _numel([d for i, d in enumerate(lhs)
                if i not in set(lc) | set(lb)])
    n_contract = set(rc)
    n_batch = set(_rb)
    n = _numel([d for i, d in enumerate(rhs)
                if i not in n_contract | n_batch])
    return 2 * b * m * n * k


def _conv_flops(eqn) -> int:
    dn = eqn.params["dimension_numbers"]
    rhs_spec = getattr(dn, "rhs_spec", None)
    avals = list(_operand_avals(eqn))
    rhs = avals[1].shape if len(avals) > 1 else ()
    out = _out_numel(eqn)
    if rhs_spec is None or not rhs:
        return 2 * out
    out_feature_dim = rhs_spec[0]
    k_prod = _numel(rhs) // max(int(rhs[out_feature_dim]), 1)
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    return 2 * out * (k_prod // max(groups, 1))


def _log2ceil(n: int) -> int:
    return max(1, math.ceil(math.log2(max(int(n), 2))))


def sort_width(eqn) -> int:
    """Length of the dimension a `sort` eqn actually sorts along —
    the cost driver. `jnp.median(table, axis=0)` sorts a [5, 500000]
    operand along dimension 0: half a million independent 5-wide
    sorts, nothing like a 500000-wide sorting network; pricing (or
    flagging, audit AU003) by the trailing dim would be wrong by 5e5."""
    shapes = [a.shape for a in _operand_avals(eqn) if a.shape]
    if not shapes:
        return 2
    dim = eqn.params.get("dimension")
    if dim is None:
        dim = len(shapes[0]) - 1
    return int(shapes[0][dim])


def sub_jaxprs(value) -> Iterable:
    """Jaxpr-like objects inside one eqn param value (ClosedJaxpr has
    `.jaxpr.eqns`, raw Jaxpr has `.eqns`), by duck typing."""
    vals = value if isinstance(value, (list, tuple)) else [value]
    for v in vals:
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(inner, "eqns"):
            yield inner
        elif hasattr(v, "eqns"):
            yield v


def _container_multiplier(eqn) -> int:
    name = eqn.primitive.name
    if name == "scan":
        return max(int(eqn.params.get("length", 1) or 1), 1)
    if name == "pallas_call":
        gm = eqn.params.get("grid_mapping")
        grid = getattr(gm, "grid", None) if gm is not None else None
        if grid is None:
            grid = eqn.params.get("grid", ())
        try:
            return max(_numel(grid), 1)
        except (TypeError, ValueError):
            return 1
    return 1


def jaxpr_cost(jaxpr) -> Cost:
    """Price one jaxpr (Closed or raw), recursively."""
    inner = getattr(jaxpr, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        jaxpr = inner
    cost = Cost()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CONTAINERS or any(
                True for v in eqn.params.values()
                for _ in sub_jaxprs(v)):
            mult = _container_multiplier(eqn)
            if name == "while":
                cost.dynamic_loops += 1
            subs = [s for v in eqn.params.values()
                    for s in sub_jaxprs(v)]
            if name == "cond":
                # price the most expensive branch (the dispatched
                # round takes one; max is the conservative choice)
                branch_costs = [jaxpr_cost(s) for s in subs]
                if branch_costs:
                    cost.merge(max(branch_costs,
                                   key=lambda c: (c.flops,
                                                  c.hbm_bytes)), mult)
            else:
                for s in subs:
                    cost.merge(jaxpr_cost(s), mult)
            continue
        if name in ("dot_general",):
            cost.add(name, _dot_flops(eqn), _eqn_bytes(eqn))
        elif name == "conv_general_dilated":
            cost.add(name, _conv_flops(eqn), _eqn_bytes(eqn))
        elif name == "sort":
            n = max((_numel(a.shape) for a in _operand_avals(eqn)),
                    default=0)
            cost.add(name, n * _log2ceil(sort_width(eqn)),
                     _eqn_bytes(eqn))
        elif name in ("top_k", "approx_top_k"):
            n = max((_numel(a.shape) for a in _operand_avals(eqn)),
                    default=0)
            k = int(eqn.params.get("k",
                                   eqn.params.get("reduction_input_size_override",
                                                  2)) or 2)
            cost.add(name, n * _log2ceil(abs(k)), _eqn_bytes(eqn))
        elif name in _REDUCERS:
            n = sum(_numel(a.shape) for a in _operand_avals(eqn))
            cost.add(name, n, _eqn_bytes(eqn))
        elif name in _DATA_MOVEMENT:
            cost.add(name, 0, _eqn_bytes(eqn))
        else:
            # elementwise default: one op per output element
            cost.add(name, _out_numel(eqn), _eqn_bytes(eqn))
    return cost


# ---------------------------------------------------------------------------
# per-link collective cost (graftmesh, ISSUE 8)
#
# The FLOPs/HBM model above prices a program as if it ran on one
# device; the collective model below prices its COMMUNICATION under an
# explicit mesh, split by link class — intra-slice ICI vs inter-slice
# DCN — because the round engine's scaling contract is stated in
# exactly those terms (parallel/mesh.make_multihost_client_mesh: one
# table-sized all-reduce crosses DCN per round, model-axis collectives
# never do). Like the FLOPs model it is a MODEL, not a prediction:
# every collective is priced as a hierarchical ring (one ring stage
# per slice over ICI, one ring over the slices for the DCN stage),
# all-reduce at factor 2 (reduce-scatter + all-gather), everything
# else at factor 1. The absolute bytes are approximate; what the
# meshaudit baseline gates on is their STABILITY and their SPLIT —
# a new collective, a payload that grew, or traffic moving from ICI
# to DCN all change the report exactly.

# collective primitive names -> byte factor over the payload; the
# payload is operand bytes (reduce-type) or output bytes (all_gather,
# whose logical payload is the gathered result)
_COLLECTIVE_FACTORS = {
    "psum": 2, "psum2": 2, "psum_invariant": 2, "pmax": 2, "pmin": 2,
    "all_gather": 1, "all_gather_invariant": 1, "reduce_scatter": 1,
    "all_to_all": 1, "ppermute": 1, "pbroadcast": 1,
}
_OUTPUT_PAYLOAD = frozenset({"all_gather", "all_gather_invariant"})


@dataclasses.dataclass(frozen=True)
class MeshLinkModel:
    """Link-class description of one mesh, consumed by
    `collective_cost`. Deliberately jax-free: the shardaudit tier
    builds one from a real jax Mesh + slice map; tests can construct
    them directly.

    axis_sizes:  {axis name: device count along it}
    axis_slices: {axis name: number of DISTINCT slices one group along
                  that axis spans}. 1 means the axis is pure ICI; S > 1
                  means a collective over it must run a DCN stage over
                  S slice groups (with size/S devices per slice on ICI).
    """
    name: str
    axis_sizes: Tuple[Tuple[str, int], ...]
    axis_slices: Tuple[Tuple[str, int], ...]

    def size(self, axis: str) -> int:
        return dict(self.axis_sizes).get(axis, 1)

    def slices(self, axis: str) -> int:
        return dict(self.axis_slices).get(axis, 1)

    def as_dict(self) -> dict:
        return {"axes": {a: n for a, n in self.axis_sizes},
                "slices": {a: s for a, s in self.axis_slices}}


@dataclasses.dataclass
class CollectiveRecord:
    """One collective equation, priced. `mult` is the container
    multiplier (a collective inside a scanned span of N rounds runs N
    times; bytes below already include it)."""
    kind: str
    axes: Tuple[str, ...]
    payload_bytes: int               # one execution's logical payload
    operand_shapes: Tuple[Tuple[int, ...], ...]
    mult: int
    ici_bytes: int                   # mult-inclusive
    dcn_bytes: int                   # mult-inclusive
    crosses_dcn: bool


class CollectiveCost:
    """Per-link rollup of every collective in one program."""

    def __init__(self):
        self.records: List[CollectiveRecord] = []
        self.ici_bytes = 0
        self.dcn_bytes = 0
        self.dcn_collectives = 0     # mult-inclusive executions

    def add(self, rec: CollectiveRecord) -> None:
        self.records.append(rec)
        self.ici_bytes += rec.ici_bytes
        self.dcn_bytes += rec.dcn_bytes
        if rec.crosses_dcn:
            self.dcn_collectives += rec.mult

    def as_dict(self) -> dict:
        """Canonical JSON-able per-link report (bit-stable ordering)."""
        by_kind: Dict[str, Dict[str, int]] = {}
        for r in self.records:
            row = by_kind.setdefault(r.kind, {"count": 0, "bytes": 0})
            row["count"] += r.mult
            row["bytes"] += r.ici_bytes + r.dcn_bytes
        return {
            "ici_bytes": self.ici_bytes,
            "dcn_bytes": self.dcn_bytes,
            "dcn_collectives": self.dcn_collectives,
            "collectives": {k: dict(by_kind[k]) for k in sorted(by_kind)},
        }


def eqn_collective_axes(eqn) -> Tuple[str, ...]:
    """Named mesh axes one collective eqn spans (positional axis
    indices — vmapped collectives — carry no mesh link and are
    skipped)."""
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _price_collective(eqn, link: MeshLinkModel, mult: int
                      ) -> Optional[CollectiveRecord]:
    kind = eqn.primitive.name
    factor = _COLLECTIVE_FACTORS[kind]
    axes = eqn_collective_axes(eqn)
    if not axes:
        return None
    if kind in _OUTPUT_PAYLOAD:
        payload = sum(aval_bytes(v.aval) for v in eqn.outvars)
    else:
        payload = sum(aval_bytes(a) for a in _operand_avals(eqn))
    ici = dcn = 0
    crosses = False
    # hierarchical ring, axis by axis: S slice groups of n/S devices —
    # each slice group rings the payload over ICI, then one ring over
    # the S groups crosses DCN with the full payload
    for a in axes:
        n = link.size(a)
        s = max(link.slices(a), 1)
        n_inner = max(n // s, 1)
        ici += factor * (n_inner - 1) * payload * s
        if s > 1:
            dcn += factor * (s - 1) * payload
            crosses = True
    return CollectiveRecord(
        kind=kind, axes=axes, payload_bytes=payload,
        operand_shapes=tuple(tuple(int(d) for d in a.shape)
                             for a in _operand_avals(eqn)),
        mult=mult, ici_bytes=ici * mult, dcn_bytes=dcn * mult,
        crosses_dcn=crosses)


def collective_cost(jaxpr, link: MeshLinkModel) -> CollectiveCost:
    """Walk one jaxpr (Closed or raw) and price every collective over
    `link`'s axes, carrying container multipliers (scan trip counts)
    exactly like `jaxpr_cost`."""
    inner = getattr(jaxpr, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        jaxpr = inner
    cost = CollectiveCost()

    def walk(jx, mult):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _COLLECTIVE_FACTORS:
                rec = _price_collective(eqn, link, mult)
                if rec is not None:
                    cost.add(rec)
            sub_mult = mult * _container_multiplier(eqn)
            for v in eqn.params.values():
                for s in sub_jaxprs(v):
                    walk(s, sub_mult)

    walk(jaxpr, 1)
    return cost


# ---------------------------------------------------------------------------
# reassociation ulp bound (graftnum, ISSUE 18)
#
# Floating-point addition is not associative: summing the same n shard
# contributions in two different association orders can differ by up to
# (n - 1) rounding steps — the textbook worst-case forward bound for
# recursive summation, |err| <= (n - 1) * eps * sum|x| (Higham, ch. 4),
# i.e. (n - 1) result-ulps per element. Within one compiled program XLA
# fixes the reduction order, so single-device replay is bit-exact; the
# order that is NOT fixed by any spec is the cross-shard combine of a
# psum-class collective (topology, ring direction, and slice layout all
# legally reassociate it). graftnum therefore PRICES that exposure
# instead of flagging it: per program, the sum over sum-type
# collectives of container-multiplier x (participants - 1), an integer
# that moves exactly when a program adds a collective, widens an axis,
# or scans more rounds per dispatch — and is diffed exact-match in
# graftnum.baseline.json like FLOPs/HBM are in audit.baseline.json.

# sum-type collectives only: pmax/pmin are exact order-free selections
# and the data-movement collectives (all_gather, ppermute, all_to_all,
# pbroadcast) round nothing
_REASSOC_COLLECTIVES = frozenset({
    "psum", "psum2", "psum_invariant", "reduce_scatter",
})


def _reduces_floats(eqn) -> bool:
    return any(str(getattr(a, "dtype", "")).startswith(("float",
                                                        "bfloat"))
               for a in _operand_avals(eqn))


def reassociation_ulp_bound(jaxpr, axis_sizes: Dict[str, int],
                            default_axis_size: int = 2) -> int:
    """Worst-case per-element ulp divergence between two legal
    reassociations of `jaxpr`'s cross-shard sum reductions.

    `axis_sizes` maps named mesh axes to participant counts (an axis
    the caller did not declare prices at `default_axis_size` — the
    smallest exposure a real multi-participant axis can have, so an
    unregistered axis is never silently free). Integer psums are exact
    and price 0. Deterministic given the jaxpr, like jaxpr_cost."""
    inner = getattr(jaxpr, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        jaxpr = inner
    total = 0

    def walk(jx, mult):
        nonlocal total
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _REASSOC_COLLECTIVES and _reduces_floats(eqn):
                n = 1
                for a in eqn_collective_axes(eqn):
                    n *= max(int(axis_sizes.get(a, default_axis_size)),
                             1)
                if n > 1:
                    total += mult * (n - 1)
            sub_mult = mult * _container_multiplier(eqn)
            for v in eqn.params.values():
                for s in sub_jaxprs(v):
                    walk(s, sub_mult)

    walk(jaxpr, 1)
    return int(total)
