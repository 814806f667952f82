"""graftnum: jaxpr-level numerics & determinism auditor (ISSUE 18).

graftaudit prices WHAT the round programs compute (FLOPs/HBM),
graftmesh WHERE the bytes move, graftsync HOW the host threads
interleave. This module is the FIFTH analysis tier: it walks the same
traced ClosedJaxprs with a dtype/finiteness dataflow lattice and
checks the contracts FetchSGD's convergence argument actually rests
on — that error feedback accumulates an exact f32 residual, that a
poisoned client's NaN/inf cannot leak through the admission
arithmetic, that every zero-survivor denominator is guarded, and that
a crash->resume replay re-dispatches bit-identical programs:

  NU001  NaN-unsafe mask arithmetic: a possibly-non-finite value
         combined with a 0/1 mask via MULTIPLY instead of
         select/where — the PR-16 bug class (NaN * 0 == NaN, so
         `t * mask` propagates a poisoned update into the masked-out
         lanes; `where(admitted > 0, t, 0)` does not). Finiteness
         provenance is tracked per value from the in-program
         injection sites (the poison/attack `where(flag, inf, t)`
         selects, the nanmedian NaN sentinel, unproven divisions)
         through aggregation and error feedback.
  NU002  precision-change audit: every lossy `convert_element_type`
         (float narrowing, float -> int8/int16 quantization) must
         match a (src, dst) seam registered in
         analysis/domains.PRECISION_SEAMS — the PR-6 quantize/
         dequantize pair, the flash-attention output cast — so a new
         silent downcast on a path the analysis assumes exact is an
         audit error, not a convergence mystery. The error-feedback
         residual operands themselves (any program input/output whose
         leaf name contains "err") are asserted f32-or-wider.
  NU003  unguarded division/rsqrt/log/sqrt: denominators and
         rsqrt/log arguments must be provably bounded away from zero
         through the lattice, sqrt arguments provably non-negative —
         the eps-max (`maximum(total, 1.0)`), where-guard, and
         survivor-count+1 idioms all prove; a raw data-dependent
         denominator does not. Zero-survivor safety becomes
         mechanical instead of per-PR vigilance.
  NU004  replay-determinism: primitives whose result is not fixed by
         any spec inside programs covered by the crash->resume
         bit-exactness contract — scatters in PROMISE_IN_BOUNDS mode
         (out-of-bounds behavior undefined), `approx_max_k` whose
         recall_target is not the pinned value, unstable sorts (tie
         order unspecified). Cross-shard psum reassociation is NOT
         flagged but PRICED: costmodel.reassociation_ulp_bound gives
         each program a worst-case ulp divergence integer, diffed
         exact-match in graftnum.baseline.json like FLOPs/HBM.
  NU005  ulp-bound drift vs graftnum.baseline.json (new / stale /
         moved program) — the baseline-drift rule, exit code 2.

The lattice is an abstract interpretation over the jaxpr: per value
it tracks {finite, nonneg, nonzero (bounded away from zero), mask
(0/1 indicator)}. Program inputs are assumed finite — non-finiteness
is tracked from where the PROGRAM introduces it (non-finite constants
routed through a select, divisions with unproven denominators).
`select_n` is the sanctioned guard point: its output is
finite-by-contract (that the predicate is semantically sufficient is
the runtime NumericSanitizer's job — the static rule enforces that
the guard IS a select, which is exactly the PR-16 contract), UNLESS a
branch is a non-finite CONSTANT, which marks an injection site (the
poison `where(flag, inf, t)`, the nanmedian sentinel) and starts
provenance instead of laundering it.

Shares graftaudit's machinery end to end: the audit-config registry
and tracers (audit.audit_configs/build_workload/trace_variant/
trace_state_motion, plus the scanned span via round.
stack_batch_for_span), the AuditBaseline exact-match diff (the ulp
block parameterizes COST_KEY/COST_FIELDS exactly like graftmesh's
byte report), the 0 clean / 1 violations / 2 baseline-drift exit
contract, and the journaled sha256 report digest
(`num_audit_digest`, bit-identical across runs).

Import discipline: jax is imported LAZILY inside the functions that
trace; `main` pins JAX_PLATFORMS=cpu first, so importing this module
stays jax-free (console-script resolution, graftlint's pure-AST pass).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from commefficient_tpu.analysis.audit import (
    AUDIT_GEOMETRY, AUDIT_POPULATION, AuditBaseline, AuditFinding,
    audit_configs, build_workload, exit_code, iter_eqns,
    trace_state_motion, trace_variant, _dtype_of, _leaf_names,
    _shape_of,
)
from commefficient_tpu.analysis.costmodel import (
    reassociation_ulp_bound, sub_jaxprs,
)
from commefficient_tpu.analysis.domains import precision_seam_pairs

NUM_RULE_DOCS = {
    "NU001": "NaN-unsafe mask arithmetic: possibly-non-finite value "
             "multiplied by a 0/1 mask (NaN*0 == NaN — the PR-16 "
             "class; use jnp.where/select)",
    "NU002": "unregistered precision downcast (not in analysis/"
             "domains.PRECISION_SEAMS), or a sub-f32 error-feedback "
             "residual operand",
    "NU003": "unguarded division/rsqrt/log/sqrt: argument not "
             "provably bounded away from zero (or non-negative, for "
             "sqrt) through the lattice",
    "NU004": "replay-nondeterministic primitive under the "
             "crash->resume bit-exactness contract (promise_in_bounds "
             "scatter, unpinned approx_max_k recall_target, unstable "
             "sort)",
    "NU005": "worst-case ulp-bound drift vs graftnum.baseline.json "
             "(new / stale / moved program)",
}

# NU004: the one recall_target the replay contract pins (jax's
# default; every shipped approx_max_k call site inherits it). A
# different value in a traced program means someone changed the
# selection accuracy without re-pricing the estimate residual.
PINNED_RECALL_TARGETS = (0.95,)

# the participant counts the ulp bound prices cross-shard reassociation
# at: the tier-1 simulated mesh's 8-device clients axis (the audit
# itself traces on a 1-device mesh so per-shard shapes stay
# host-count-independent — the bound prices the DECLARED deployment
# axis, not the tracing mesh). An axis not listed here prices at the
# costmodel default (2) so a new axis is never silently free.
ULP_AXIS_SIZES = {"clients": 8, "model": 2}

# the scanned-span length graftnum traces (matches the mesh tier's
# span: long enough that the scan carry is live, short enough to
# trace in milliseconds)
SPAN_LEN = 2


# ---------------------------------------------------------------------------
# the dtype/finiteness lattice


@dataclasses.dataclass(frozen=True)
class Absval:
    """Abstract value: what the lattice can PROVE about one jaxpr
    value. Each flag is evidence, not truth — False means "not
    proven", never "proven false".

    finite:   cannot be NaN/inf.
    nonneg:   no negative finite values (NaN lanes allowed — squares
              and abs are nonneg even of possibly-NaN inputs, which
              is what the sqrt rule needs).
    nonzero:  finite AND bounded away from zero — safe as a
              denominator / rsqrt / log argument.
    mask:     a {0, 1} indicator (comparison result, is_finite,
              bool cast, product of masks).
    ptrue / pfalse: a predicate provably all-True / all-False —
              abstract constant folding, so a DEFENSIVE NaN select
              (jnp.median's `where(any(x != x), nan, x)` over a
              proven-finite x) resolves to its live branch instead of
              reading as an injection site.
    const_nonfinite: a non-finite CONSTANT (inf/nan literal, possibly
              broadcast/reshaped) — the select_n injection-site
              marker; ordinary computed non-finiteness never sets it.
    src:      human-readable provenance of the first non-finite
              source, carried for NU001 messages.
    """
    finite: bool = True
    nonneg: bool = False
    nonzero: bool = False
    mask: bool = False
    ptrue: bool = False
    pfalse: bool = False
    const_nonfinite: bool = False
    src: str = ""


_DEFAULT = Absval()
_BOOL = Absval(finite=True, nonneg=True, nonzero=False, mask=True)
_INT = Absval(finite=True)


def _join(*vals: Absval) -> Absval:
    """Lattice meet over control-flow joins: a property holds of the
    join only if it holds of every incoming value."""
    if not vals:
        return _DEFAULT
    return Absval(
        finite=all(v.finite for v in vals),
        nonneg=all(v.nonneg for v in vals),
        nonzero=all(v.nonzero for v in vals),
        mask=all(v.mask for v in vals),
        ptrue=all(v.ptrue for v in vals),
        pfalse=all(v.pfalse for v in vals),
        const_nonfinite=any(v.const_nonfinite for v in vals),
        src=next((v.src for v in vals if v.src), ""))


def _const_absval(val) -> Absval:
    """Absval of a concrete constant (jaxpr Literal / closed const)."""
    import numpy as np
    try:
        arr = np.asarray(val)
    except (TypeError, ValueError):
        # an abstract/token const with no concrete value
        return _DEFAULT
    kind = arr.dtype.kind
    if kind == "b":
        return dataclasses.replace(
            _BOOL,
            ptrue=bool(arr.all()) if arr.size else False,
            pfalse=bool((~arr).all()) if arr.size else False)
    if kind in "iu":
        return Absval(
            finite=True,
            nonneg=bool((arr >= 0).all()) if arr.size else True,
            nonzero=bool((arr != 0).all()) if arr.size else False,
            mask=bool(np.isin(arr, (0, 1)).all()) if arr.size else False)
    if kind in "fV":  # V: bfloat16 registers as void on some numpy
        try:
            farr = arr.astype(np.float64)
        except (TypeError, ValueError):
            return _DEFAULT
        if not farr.size:
            return Absval(finite=True)
        fin = bool(np.isfinite(farr).all())
        return Absval(
            finite=fin,
            nonneg=fin and bool((farr >= 0).all()),
            nonzero=fin and bool((np.abs(farr) > 0).all()),
            mask=fin and bool(np.isin(farr, (0.0, 1.0)).all()),
            const_nonfinite=not fin,
            src="" if fin else "a non-finite constant (inf/nan "
                               "literal)")
    return _DEFAULT


def _is_float_dtype(dt) -> bool:
    return str(dt).startswith(("float", "bfloat"))


def _site(eqn) -> str:
    """`path:line (function)` of the deepest in-repo frame that traced
    this eqn — so a finding lands on the source idiom, not the jaxpr.
    Best-effort: tracing through library combinators can leave no
    user frame."""
    tb = getattr(getattr(eqn, "source_info", None), "traceback", None)
    if tb is None:
        return "<no source info>"
    best = None
    for fr in tb.frames:
        fn = fr.file_name.replace("\\", "/")
        if "commefficient_tpu/" in fn and "/analysis/" not in fn:
            best = fr
            break  # frames run innermost-out: first hit is deepest
    if best is None:
        return "<no in-repo frame>"
    short = best.file_name.replace("\\", "/")
    short = short[short.rindex("commefficient_tpu/"):]
    return f"{short}:{best.line_num} ({best.function_name})"


# primitives that only move/reshape/retype data: every lattice
# property of the (single data) operand survives. pvary/pcast are the
# casts jax 0.9.0 inserts under shard_map's check_vma — also around a
# literal guard like the 1.0 of jnp.maximum(count, 1.0) — and change a
# value's varying-axes type, never the value.
_SHAPE_ONLY = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze",
    "expand_dims", "rev", "copy", "stop_gradient", "slice",
    "device_put", "sharding_constraint", "convert_element_type",
    "real", "reduce_precision", "pvary", "pcast",
})

# gather-class: output elements are a subset of operand 0's elements
_GATHER_LIKE = frozenset({"gather", "dynamic_slice", "take"})

# bool-producing comparisons / predicates -> mask
_MASK_PRIMS = frozenset({
    "eq", "ne", "gt", "lt", "ge", "le", "is_finite", "and", "or",
    "not", "xor", "reduce_and", "reduce_or", "eq_to", "lt_to",
})

_SCATTER_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min",
    "scatter-max", "scatter-apply",
})


class _LatticeAuditor:
    """One program's NU001/NU003 walk: abstract-interpret the jaxpr,
    recording findings at the unsafe-combination sites."""

    def __init__(self, program: str):
        self.program = program
        self.findings: List[AuditFinding] = []

    # -------------------- environment ---------------------------------
    def _read(self, env: Dict[int, Absval], v) -> Absval:
        val = getattr(v, "val", None)
        if val is not None and not hasattr(v, "count"):
            # a Literal: carries its concrete value
            return _const_absval(val)
        return env.get(id(v), _DEFAULT)

    # -------------------- drivers -------------------------------------
    def run(self, closed) -> None:
        jaxpr = closed.jaxpr
        env: Dict[int, Absval] = {}
        for cv, const in zip(jaxpr.constvars, closed.consts):
            env[id(cv)] = _const_absval(const)
        # program inputs are assumed finite: non-finiteness is tracked
        # from where the program INTRODUCES it (module docstring)
        for v in jaxpr.invars:
            env[id(v)] = Absval(finite=True)
        self._analyze(jaxpr, env, collect=True)

    def _analyze(self, jx, env: Dict[int, Absval],
                 collect: bool) -> None:
        for eqn in jx.eqns:
            subs = [s for v in eqn.params.values()
                    for s in sub_jaxprs(v)]
            if subs:
                self._container(eqn, subs, env, collect)
            else:
                outs = self._transfer(
                    eqn, [self._read(env, v) for v in eqn.invars],
                    collect)
                for ov, oval in zip(eqn.outvars, outs):
                    env[id(ov)] = oval

    def _container(self, eqn, subs, env: Dict[int, Absval],
                   collect: bool) -> None:
        """Propagate through a sub-jaxpr-bearing eqn (pjit, scan,
        while, cond, shard_map, pallas_call, custom_*): seed inner
        invars by positional tail alignment (audit.population_scan's
        convention — cond's leading predicate and scan's layout both
        align from the end), run each sub, join outvars across subs.
        scan/while bodies run twice so properties that only break on
        the second trip (a carry degrading) are not over-proven;
        findings collect on the final pass only."""
        loops = eqn.primitive.name in ("scan", "while")
        passes = 2 if loops else 1
        out_joined: Dict[int, List[Absval]] = {}
        for p in range(passes):
            final = p == passes - 1
            out_joined.clear()
            for s in subs:
                sub_env: Dict[int, Absval] = dict(env)
                n_in = min(len(eqn.invars), len(s.invars))
                for ev, sv in zip(eqn.invars[-n_in:],
                                  s.invars[-n_in:]):
                    sub_env[id(sv)] = self._read(env, ev)
                self._analyze(s, sub_env, collect and final)
                n_out = min(len(eqn.outvars), len(s.outvars))
                for ev, sv in zip(eqn.outvars[-n_out:],
                                  s.outvars[-n_out:]):
                    out_joined.setdefault(id(ev), []).append(
                        sub_env.get(id(sv), _DEFAULT))
            if loops and passes > 1 and p == 0:
                # feed the first pass's outputs back in as the next
                # pass's carry seeds (joined with the initial values)
                for s in subs:
                    n_out = min(len(eqn.outvars), len(s.outvars))
                    for ev, sv in zip(eqn.outvars[-n_out:],
                                      s.outvars[-n_out:]):
                        prev = env.get(id(ev))
                        joined = _join(*out_joined[id(ev)])
                        env[id(ev)] = (_join(prev, joined)
                                       if prev is not None else joined)
        for ev in eqn.outvars:
            vals = out_joined.get(id(ev))
            # const_nonfinite never crosses a container boundary: the
            # select-injection marker is local to the eqn stream that
            # owns the literal
            joined = (_join(*vals) if vals else _DEFAULT)
            env[id(ev)] = dataclasses.replace(joined,
                                              const_nonfinite=False)

    # -------------------- findings ------------------------------------
    def _hit(self, rule: str, eqn, message: str) -> None:
        self.findings.append(AuditFinding(
            self.program, rule, message + " [at " + _site(eqn) + "]"))

    # -------------------- transfer ------------------------------------
    def _transfer(self, eqn, ins: List[Absval],
                  collect: bool) -> List[Absval]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if name in _MASK_PRIMS:
            # abstract predicate folding (Absval docstring): enough
            # boolean algebra to prove jnp.median's defensive
            # `any(x != x)` dead over a proven-finite x
            same = (len(eqn.invars) == 2
                    and eqn.invars[0] is eqn.invars[1])
            a, b = (ins + [_BOOL, _BOOL])[:2]
            out = _BOOL
            if name == "ne" and same and a.finite:
                out = dataclasses.replace(_BOOL, pfalse=True)
            elif name == "eq" and same and a.finite:
                out = dataclasses.replace(_BOOL, ptrue=True)
            elif name == "is_finite" and a.finite:
                out = dataclasses.replace(_BOOL, ptrue=True)
            elif name == "not":
                out = dataclasses.replace(_BOOL, ptrue=a.pfalse,
                                          pfalse=a.ptrue)
            elif name == "and":
                out = dataclasses.replace(
                    _BOOL, ptrue=a.ptrue and b.ptrue,
                    pfalse=a.pfalse or b.pfalse)
            elif name == "or":
                out = dataclasses.replace(
                    _BOOL, ptrue=a.ptrue or b.ptrue,
                    pfalse=a.pfalse and b.pfalse)
            elif name in ("reduce_or", "reduce_and"):
                out = dataclasses.replace(_BOOL, ptrue=a.ptrue,
                                          pfalse=a.pfalse)
            return [out] * n_out

        if name in _SHAPE_ONLY:
            base = ins[0] if ins else _DEFAULT
            if name == "convert_element_type":
                src = _dtype_of(eqn.invars[0]) if eqn.invars else None
                dst = eqn.params.get("new_dtype")
                if (src is not None and not _is_float_dtype(src)
                        and _is_float_dtype(dst)):
                    # int/bool -> float: exact, and ints are finite
                    base = dataclasses.replace(base, finite=True)
                if dst is not None and not _is_float_dtype(dst):
                    # -> int/bool: whatever it was, it is finite now
                    base = dataclasses.replace(base, finite=True,
                                               const_nonfinite=False)
            return [base] * n_out

        if name in _GATHER_LIKE:
            # a subset of operand 0's elements (indices are operand 1+)
            base = ins[0] if ins else _DEFAULT
            return [dataclasses.replace(base, const_nonfinite=False)
                    ] * n_out

        if name == "select_n":
            pred, branches = ins[0], ins[1:]
            # predicate folding first: a select whose predicate is
            # proven constant IS its live branch — the defensive
            # library NaN select over proven-finite data resolves
            # here instead of reading as an injection
            if branches and pred.pfalse:
                return [branches[0]] * n_out
            if len(branches) == 2 and pred.ptrue:
                return [branches[1]] * n_out
            injected = [b for b in branches if b.const_nonfinite]
            if injected:
                return [Absval(
                    finite=False,
                    src="a non-finite constant routed through "
                        "select/where (a poison/attack injection or "
                        "NaN-sentinel site)")] * n_out
            # the sanctioned guard point: finite-by-contract (module
            # docstring); the other properties must hold of every
            # branch
            j = _join(*branches) if branches else _DEFAULT
            return [dataclasses.replace(j, finite=True,
                                        const_nonfinite=False)] * n_out

        if name == "mul":
            a, b = (ins + [_DEFAULT, _DEFAULT])[:2]
            if collect:
                pairs = ((a, eqn.invars[0], b), (b, eqn.invars[-1], a))
                for m, mvar, v in pairs:
                    # a scalar {0,1} factor (a literal 1.0 scale, a
                    # traced enable flag) is not mask ARITHMETIC —
                    # the PR-16 class is a per-lane indicator array
                    if (m.mask and not v.finite
                            and len(_shape_of(mvar) or ()) >= 1):
                        self._hit("NU001", eqn, (
                            "possibly-non-finite value ("
                            + (v.src or "unproven finiteness")
                            + ") multiplied by a 0/1 mask at `mul` "
                            "over " + str(_shape_of(eqn.outvars[0]))
                            + ": NaN*0 == NaN, so the masked-OUT "
                            "lanes still propagate the poison — use "
                            "jnp.where(mask > 0, value, 0) (the "
                            "PR-16 admission idiom), which this "
                            "audit treats as the guard point"))
                        break
            same = (len(eqn.invars) == 2
                    and eqn.invars[0] is eqn.invars[1])
            return [Absval(
                finite=a.finite and b.finite,
                nonneg=same or (a.nonneg and b.nonneg),
                nonzero=a.nonzero and b.nonzero,
                mask=a.mask and b.mask,
                src=a.src or b.src)] * n_out

        if name == "div":
            num, den = (ins + [_DEFAULT, _DEFAULT])[:2]
            den_dt = (_dtype_of(eqn.invars[1])
                      if len(eqn.invars) > 1 else None)
            if (collect and den_dt is not None
                    and _is_float_dtype(den_dt) and not den.nonzero):
                self._hit("NU003", eqn, (
                    "`div` denominator over "
                    + str(_shape_of(eqn.invars[1]))
                    + " is not provably bounded away from zero: a "
                    "zero-survivor round (or a poisoned count) makes "
                    "this inf/NaN — guard with jnp.maximum(denom, "
                    "eps), a survivor-count+1, or a where-guard"))
            return [Absval(
                finite=num.finite and den.nonzero,
                nonneg=num.nonneg and den.nonneg,
                nonzero=num.nonzero and den.nonzero,
                src=num.src or den.src
                or ("" if den.nonzero else
                    "a division with an unproven denominator"))
            ] * n_out

        if name == "sqrt":
            a = ins[0] if ins else _DEFAULT
            dt = _dtype_of(eqn.invars[0]) if eqn.invars else None
            if (collect and dt is not None and _is_float_dtype(dt)
                    and not a.nonneg):
                self._hit("NU003", eqn, (
                    "`sqrt` argument over "
                    + str(_shape_of(eqn.invars[0]))
                    + " is not provably non-negative: a negative "
                    "lane is a silent NaN — square/abs the operand "
                    "or clamp at 0"))
            return [Absval(finite=a.finite and a.nonneg, nonneg=True,
                           nonzero=a.nonzero and a.nonneg,
                           src=a.src)] * n_out

        if name in ("rsqrt", "log", "log1p"):
            a = ins[0] if ins else _DEFAULT
            dt = _dtype_of(eqn.invars[0]) if eqn.invars else None
            positive = a.nonneg and a.nonzero
            # log1p's domain is x > -1; a proven-nonneg argument is
            # enough for it
            ok = a.nonneg if name == "log1p" else positive
            if (collect and dt is not None and _is_float_dtype(dt)
                    and not ok):
                self._hit("NU003", eqn, (
                    "`" + name + "` argument over "
                    + str(_shape_of(eqn.invars[0]))
                    + " is not provably bounded away from zero: "
                    "guard with jnp.maximum(x, eps) before the "
                    "reciprocal/log"))
            return [Absval(finite=a.finite and ok,
                           nonneg=name == "rsqrt",
                           nonzero=name == "rsqrt" and ok,
                           src=a.src)] * n_out

        if name == "integer_pow":
            a = ins[0] if ins else _DEFAULT
            y = int(eqn.params.get("y", 1) or 1)
            if y < 0:
                # x**-n is a division: same proof obligation
                dt = _dtype_of(eqn.invars[0]) if eqn.invars else None
                if (collect and dt is not None and _is_float_dtype(dt)
                        and not a.nonzero):
                    self._hit("NU003", eqn, (
                        "`integer_pow` with negative exponent "
                        + str(y) + " over "
                        + str(_shape_of(eqn.invars[0]))
                        + ": a reciprocal of a value not provably "
                        "bounded away from zero"))
                return [Absval(finite=a.finite and a.nonzero,
                               nonneg=y % 2 == 0 or a.nonneg,
                               nonzero=a.nonzero, src=a.src)] * n_out
            return [Absval(finite=a.finite,
                           nonneg=y % 2 == 0 or a.nonneg,
                           nonzero=a.nonzero and y > 0,
                           src=a.src)] * n_out

        if name in ("abs", "square"):
            a = ins[0] if ins else _DEFAULT
            return [Absval(finite=a.finite, nonneg=True,
                           nonzero=a.nonzero, src=a.src)] * n_out

        if name in ("exp", "exp2", "logistic"):
            a = ins[0] if ins else _DEFAULT
            return [Absval(finite=a.finite, nonneg=True,
                           nonzero=a.finite, src=a.src)] * n_out

        if name == "add":
            a, b = (ins + [_DEFAULT, _DEFAULT])[:2]
            fin = a.finite and b.finite
            return [Absval(
                finite=fin, nonneg=a.nonneg and b.nonneg,
                nonzero=fin and ((a.nonzero and a.nonneg and b.nonneg)
                                 or (b.nonzero and b.nonneg
                                     and a.nonneg)),
                src=a.src or b.src)] * n_out

        if name == "sub":
            a, b = (ins + [_DEFAULT, _DEFAULT])[:2]
            return [Absval(finite=a.finite and b.finite,
                           src=a.src or b.src)] * n_out

        if name == "max":
            a, b = (ins + [_DEFAULT, _DEFAULT])[:2]
            fin = a.finite and b.finite
            pos_a = a.nonneg and a.nonzero
            pos_b = b.nonneg and b.nonzero
            return [Absval(
                finite=fin, nonneg=a.nonneg or b.nonneg,
                nonzero=fin and (pos_a or pos_b
                                 or (a.nonzero and b.nonzero)),
                src=a.src or b.src)] * n_out

        if name == "min":
            a, b = (ins + [_DEFAULT, _DEFAULT])[:2]
            fin = a.finite and b.finite
            return [Absval(finite=fin,
                           nonneg=a.nonneg and b.nonneg,
                           nonzero=fin and a.nonzero and b.nonzero,
                           src=a.src or b.src)] * n_out

        if name == "clamp":
            lo, x, hi = (ins + [_DEFAULT] * 3)[:3]
            fin = lo.finite and x.finite and hi.finite
            return [Absval(finite=fin, nonneg=lo.nonneg,
                           nonzero=fin and lo.nonneg and lo.nonzero,
                           src=x.src)] * n_out

        if name == "neg":
            a = ins[0] if ins else _DEFAULT
            return [Absval(finite=a.finite, nonzero=a.nonzero,
                           src=a.src)] * n_out

        if name in ("reduce_sum", "cumsum", "cumlogsumexp"):
            a = ins[0] if ins else _DEFAULT
            return [Absval(finite=a.finite, nonneg=a.nonneg,
                           src=a.src)] * n_out

        if name in ("reduce_max", "reduce_min", "cummax", "cummin"):
            a = ins[0] if ins else _DEFAULT
            return [Absval(finite=a.finite, nonneg=a.nonneg,
                           nonzero=(a.finite and a.nonzero
                                    and a.nonneg),
                           src=a.src)] * n_out

        if name in ("reduce_prod", "cumprod"):
            a = ins[0] if ins else _DEFAULT
            return [Absval(finite=a.finite, nonneg=a.nonneg,
                           src=a.src)] * n_out

        if name in ("psum", "psum2", "psum_invariant"):
            a = _join(*ins) if ins else _DEFAULT
            return [Absval(finite=a.finite, nonneg=a.nonneg,
                           nonzero=(a.finite and a.nonzero
                                    and a.nonneg),
                           src=a.src)] * n_out

        if name in ("all_gather", "all_gather_invariant", "ppermute",
                    "all_to_all",
                    "pbroadcast", "pmax", "pmin"):
            a = _join(*ins) if ins else _DEFAULT
            return [dataclasses.replace(a, const_nonfinite=False)
                    ] * n_out

        if name == "pad":
            a, pv = (ins + [_DEFAULT, _DEFAULT])[:2]
            return [_join(a, pv)] * n_out

        if name in ("concatenate", "dynamic_update_slice", "scatter",
                    "scatter-add", "select_and_scatter_add"):
            data = [v for v, iv in zip(ins, eqn.invars)
                    if _is_float_dtype(_dtype_of(iv))
                    or str(_dtype_of(iv)) == "bool"] or ins
            j = _join(*data) if data else _DEFAULT
            return [dataclasses.replace(j, const_nonfinite=False)
                    ] * n_out

        if name == "sort":
            # multi-operand sort: output i is a permutation of input i
            return [dataclasses.replace(v, const_nonfinite=False)
                    for v in (ins + [_DEFAULT] * n_out)[:n_out]]

        if name in ("top_k", "approx_top_k"):
            a = ins[0] if ins else _DEFAULT
            vals = dataclasses.replace(a, const_nonfinite=False)
            out = [vals] * n_out
            if n_out == 2:
                out[1] = _INT  # indices
            return out

        if name in ("iota", "axis_index", "program_id", "argmax",
                    "argmin", "random_fold_in", "random_wrap",
                    "random_unwrap", "random_bits", "random_seed",
                    "shift_left", "shift_right_logical",
                    "shift_right_arithmetic", "population_count",
                    "clz", "rem", "floor", "ceil", "round", "sign",
                    "nextafter"):
            # integer-producing / value-bounded prims: finite; `rem`,
            # `floor`, `ceil`, `round`, `sign`, `nextafter` keep the
            # operand's finiteness instead
            if name in ("rem", "floor", "ceil", "round", "sign",
                        "nextafter"):
                a = ins[0] if ins else _DEFAULT
                return [Absval(finite=a.finite,
                               nonneg=a.nonneg and name != "rem",
                               src=a.src)] * n_out
            return [_INT] * n_out

        # default: finite iff every float operand is proven finite;
        # nothing else survives an unknown primitive
        fin = all(v.finite for v in ins) if ins else True
        src = next((v.src for v in ins if v.src), "")
        return [Absval(finite=fin, src=src)] * n_out


def lattice_findings(program: str, closed) -> List[AuditFinding]:
    """NU001 + NU003 over one traced program."""
    auditor = _LatticeAuditor(program)
    auditor.run(closed)
    # no set-dedup (audit.forbidden_primitive_findings' rationale):
    # each unsafe site must count against the baseline individually
    return sorted(auditor.findings)


# ---------------------------------------------------------------------------
# NU002: precision seams + error-feedback width


def _is_downcast(src, dst) -> bool:
    """A LOSSY conversion: float narrowing, or float -> int8/int16
    quantization. Upcasts are exact; float -> int32/int64 is an index/
    count computation (exact for every magnitude the engine produces),
    not a precision seam."""
    import numpy as np
    try:
        s, d = np.dtype(src), np.dtype(dst)
    except TypeError:
        return False
    if _is_float_dtype(src) and _is_float_dtype(dst):
        return d.itemsize < s.itemsize
    if _is_float_dtype(src) and d.kind in "iu":
        return d.itemsize <= 2
    return False


def precision_findings(program: str, closed,
                       in_names: Sequence[str],
                       out_names: Sequence[str]) -> List[AuditFinding]:
    out: List[AuditFinding] = []
    seams = precision_seam_pairs()
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = _dtype_of(eqn.invars[0]) if eqn.invars else None
        dst = eqn.params.get("new_dtype")
        if src is None or dst is None:
            continue
        if (_is_downcast(src, dst)
                and (str(src), str(dst)) not in seams):
            out.append(AuditFinding(
                program, "NU002",
                f"unregistered precision downcast {src}->{dst} over "
                f"{_shape_of(eqn.invars[0])}: every lossy seam must "
                "be declared in analysis/domains.PRECISION_SEAMS "
                "with its residual story before it ships (the PR-6 "
                "wire quantization workflow)"))
    # error-feedback residual width: any err-named program operand
    # below f32 silently degrades the exact-residual accumulation
    # FetchSGD's convergence argument needs
    jaxpr = closed.jaxpr
    for vs, names, kind in ((jaxpr.invars, in_names, "input"),
                            (jaxpr.outvars, out_names, "output")):
        for v, name in zip(vs, names):
            if "err" not in name.lower():
                continue
            dt = _dtype_of(v)
            if dt is None or not _is_float_dtype(dt):
                continue
            import numpy as np
            if np.dtype(dt).itemsize < 4:
                out.append(AuditFinding(
                    program, "NU002",
                    f"error-feedback residual {kind} `{name}` is "
                    f"{dt}: the residual accumulation must stay "
                    "f32-or-wider end to end (the quantization "
                    "rounding it absorbs is the convergence "
                    "argument's whole budget)"))
    return sorted(out)


# ---------------------------------------------------------------------------
# NU004: replay-determinism


def determinism_findings(program: str, closed) -> List[AuditFinding]:
    out: List[AuditFinding] = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name in _SCATTER_PRIMS:
            mode = str(eqn.params.get("mode", "") or "")
            if "PROMISE_IN_BOUNDS" in mode.upper():
                out.append(AuditFinding(
                    program, "NU004",
                    f"`{name}` in PROMISE_IN_BOUNDS mode: "
                    "out-of-bounds behavior is undefined, so a "
                    "resumed replay may diverge bitwise — use the "
                    "default FILL_OR_DROP (or CLIP) mode inside "
                    "programs under the crash->resume contract"))
        elif name == "approx_top_k":
            rt = float(eqn.params.get("recall_target", 0.0) or 0.0)
            if rt not in PINNED_RECALL_TARGETS:
                out.append(AuditFinding(
                    program, "NU004",
                    f"`approx_top_k` with recall_target={rt}: the "
                    "replay contract pins "
                    f"{PINNED_RECALL_TARGETS} — an unpinned target "
                    "changes the selection (and the estimate "
                    "residual) silently across jax versions; pin it "
                    "at the call site or register the new value in "
                    "numaudit.PINNED_RECALL_TARGETS"))
        elif name == "sort":
            if eqn.params.get("is_stable") is False:
                out.append(AuditFinding(
                    program, "NU004",
                    "unstable `sort`: tie order is unspecified, so "
                    "equal keys (ubiquitous in top-k magnitude "
                    "selection) permute freely across "
                    "compilers/backends — use a stable sort inside "
                    "programs under the crash->resume contract"))
    return sorted(out)


# ---------------------------------------------------------------------------
# baseline: empty violations + the exact-match ulp block


class NumBaseline(AuditBaseline):
    """graftnum.baseline.json: {"violations": [...] (shipped EMPTY —
    real findings are FIXED, per house precedent), "ulp": {program:
    {worst_case_ulp}}}. The whole exact-match diff is inherited from
    AuditBaseline with the cost block re-parameterized, exactly like
    graftmesh's MeshBaseline."""

    COST_KEY = "ulp"
    COST_FIELDS = ("worst_case_ulp",)
    DRIFT_RULE = "NU005"


# ---------------------------------------------------------------------------
# the full audit


def trace_span(handle, server, clients, batch, lr, key,
               span_len: int = SPAN_LEN):
    """(ClosedJaxpr, invar names, outvar names) of the scanned
    `train_rounds` span program over `span_len` stacked copies of
    `batch` — the program a crash->resume drill re-dispatches, so its
    determinism walk is the one the NU004 contract is really about."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.federated.round import stack_batch_for_span
    span = stack_batch_for_span(batch, span_len)
    lrs = jnp.stack([lr] * span_len)
    closed, out_shape = jax.make_jaxpr(
        handle.train_rounds, return_shape=True)(
        server, clients, span, lrs, key)
    in_names = (_leaf_names("server", server)
                + _leaf_names("clients", clients)
                + _leaf_names("span", span)
                + _leaf_names("lr", lrs) + _leaf_names("key", key))
    return closed, in_names, _leaf_names("out", out_shape)


def run_num_audit() -> Tuple[dict, List[AuditFinding]]:
    """Trace every audit config x (round variants + the two
    state-motion programs + the scanned span) and run the numerics
    walks; return (report, findings). Findings carry NU001-NU004;
    NU005 (ulp drift) is the caller's baseline diff — the report's
    `ulp` block feeds it."""
    from commefficient_tpu.federated.round import program_variants_for

    by_program: Dict[str, Dict[str, int]] = {}
    ulp: Dict[str, Dict[str, int]] = {}
    findings: List[AuditFinding] = []

    def audit_one(prog, closed, in_names, out_names):
        fs = (lattice_findings(prog, closed)
              + precision_findings(prog, closed, in_names, out_names)
              + determinism_findings(prog, closed))
        findings.extend(fs)
        counts: Dict[str, int] = {}
        for f in fs:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        if counts:
            by_program[prog] = dict(sorted(counts.items()))
        ulp[prog] = {"worst_case_ulp": reassociation_ulp_bound(
            closed, ULP_AXIS_SIZES)}

    for cfg_name, cfg in audit_configs():
        handle, server, clients, variants, lr, key = build_workload(
            cfg)
        for variant in program_variants_for(cfg):
            closed, in_names, out_names = trace_variant(
                handle, server, clients, variants[variant], lr, key)
            audit_one(f"{cfg_name}/{variant}", closed, in_names,
                      out_names)
        motion_batch = variants.get("mask_free",
                                    variants.get("screened"))
        for motion, (closed, in_names, out_names) in \
                trace_state_motion(handle, clients,
                                   motion_batch).items():
            audit_one(f"{cfg_name}/{motion}", closed, in_names,
                      out_names)
        closed, in_names, out_names = trace_span(
            handle, server, clients, motion_batch, lr, key)
        audit_one(f"{cfg_name}/span", closed, in_names, out_names)

    rules = {r: 0 for r in NUM_RULE_DOCS}
    for f in findings:
        rules[f.rule] = rules.get(f.rule, 0) + 1
    report = {
        "version": 1,
        "geometry": dict(AUDIT_GEOMETRY, population=AUDIT_POPULATION,
                         span_len=SPAN_LEN,
                         ulp_axes=dict(ULP_AXIS_SIZES)),
        "rules": rules,
        "by_program": by_program,
        "ulp": {p: ulp[p] for p in sorted(ulp)},
        "registry": {
            "precision_seams": len(precision_seam_pairs()),
            "pinned_recall_targets": list(PINNED_RECALL_TARGETS),
        },
    }
    report["digest"] = report_digest(report)
    # no set-dedup — audit.forbidden_primitive_findings' rationale
    return report, sorted(findings)


def report_digest(report: dict) -> str:
    """sha256 over the canonical rule/ulp blocks — the bit-identical-
    across-runs claim is checked on exactly this value (same contract
    as graftaudit/graftsync)."""
    canon = json.dumps({"geometry": report["geometry"],
                        "rules": report["rules"],
                        "by_program": report["by_program"],
                        "ulp": report["ulp"],
                        "registry": report["registry"]},
                       sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()


def journal_digest(journal_path: str, report: dict,
                   findings_count: int) -> dict:
    """Append the audit's report to a run journal as a
    `num_audit_digest` event (schema checked by telemetry.journal.
    validate_journal / scripts/journal_summary.py, mirroring
    audit_digest / mesh_audit_digest / sync_audit_digest)."""
    from commefficient_tpu.telemetry.journal import append_event
    return append_event(
        journal_path, "num_audit_digest",
        digest=report["digest"],
        rules=report["rules"],
        ulp={p: d["worst_case_ulp"]
             for p, d in report["ulp"].items()},
        findings=int(findings_count))


# ---------------------------------------------------------------------------
# CLI


def _split(findings: Sequence[AuditFinding]
           ) -> Tuple[List[AuditFinding], List[AuditFinding]]:
    """(rule violations, baseline drift) — NU005 is this tier's drift
    rule (audit.split_findings keys on the *AU006 suffix, which the
    NU rule space deliberately does not reuse)."""
    violations = [f for f in findings if f.rule != "NU005"]
    drift = [f for f in findings if f.rule == "NU005"]
    return violations, drift


def main(argv: Optional[list] = None) -> int:
    # never claim an accelerator: the audit only traces
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from commefficient_tpu.analysis.engine import load_pyproject_tool
    conf = load_pyproject_tool("graftnum")
    ap = argparse.ArgumentParser(
        prog="graftnum",
        description="jaxpr-level numerics & determinism auditor: "
                    "NaN-unsafe mask arithmetic, precision seams, "
                    "zero-guard divisions, replay determinism, and "
                    "the worst-case ulp baseline (rules NU001-NU005; "
                    "see --list-rules). Exit codes: 0 clean, 1 rule "
                    "violations, 2 baseline drift only.")
    ap.add_argument("--baseline", default=conf.get(
        "baseline", "graftnum.baseline.json"),
        help="baseline file (shipped with EMPTY violations — real "
             "findings are fixed, not grandfathered — plus the "
             "exact-match per-program ulp block)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding and skip the ulp diff")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from this audit")
    ap.add_argument("--journal", default="",
                    help="append the report to this JSONL run journal "
                         "as a `num_audit_digest` event")
    ap.add_argument("--report", action="store_true",
                    help="print the full JSON report to stdout")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for code, doc in sorted(NUM_RULE_DOCS.items()):
            print(f"{code}  {doc}")
        return 0

    report, findings = run_num_audit()

    if args.write_baseline:
        counts: Dict[Tuple[str, str], int] = {}
        for f in findings:
            counts[(f.program, f.rule)] = counts.get(
                (f.program, f.rule), 0) + 1
        NumBaseline(
            {k: (n, "TODO: justify or fix") for k, n in counts.items()},
            report["ulp"]).dump(args.baseline)
        print(f"graftnum: wrote {len(findings)} grandfathered "
              f"finding(s) + {len(report['ulp'])} program ulp "
              f"bound(s) to {args.baseline}")
        return 0

    stale: List[str] = []
    if not args.no_baseline:
        baseline = (NumBaseline.load(args.baseline)
                    if os.path.exists(args.baseline) else
                    NumBaseline())
        new, stale = baseline.apply_violations(findings)
        ulp_findings = baseline.apply_costs(report["ulp"],
                                            tolerance=0.0)
        findings = sorted(new + ulp_findings)

    if args.report:
        print(json.dumps(report, indent=2, sort_keys=True))
    if args.journal:
        journal_digest(args.journal, report, len(findings))

    for f in findings:
        print(f.render())
    for msg in stale:
        print(f"graftnum: {msg}")
    # the shared exit-code contract: 1 = rule violations (NU001-NU004),
    # 2 = baseline drift only (NU005 ulp mismatch / stale entries)
    violations, drift = _split(findings)
    rc = exit_code(violations, drift, stale)
    if rc:
        print(f"graftnum: {len(violations)} violation(s), "
              f"{len(drift)} drift finding(s), {len(stale)} stale "
              f"baseline entr(ies)")
        return rc
    print(f"graftnum: clean ({len(report['ulp'])} program(s) "
          f"audited, digest {report['digest'][:12]})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
