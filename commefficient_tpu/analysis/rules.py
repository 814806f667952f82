"""graftlint rules GL001-GL015.

Each rule is a function ``check(module: ModuleInfo) -> Iterator[
Violation]`` over one parsed file. The rules are deliberately
mechanical: they encode the round engine's invariants (see
analysis/__init__ and README "Invariants & graftlint") as syntactic
patterns, erring toward precision over recall — a lint that cries wolf
gets disabled, while a narrow one that holds the line on the contracts
it CAN see stays armed in CI forever.

Traced-code scoping (GL001/GL002/GL004): a function is considered
TRACED when it is (a) decorated with ``jax.jit`` / ``vmap`` / ``pmap``
/ ``shard_map`` / ``checkpoint`` (bare or under ``partial(...)``),
(b) passed by name to ``jax.jit(f)`` / ``jax.vmap(f)`` /
``jax.lax.scan(f, ...)`` / ``jax.lax.cond(p, f, g)`` /
``shard_map(f, ...)`` / ``jax.grad(f)`` and friends anywhere in the
same file, or (c) lexically nested inside a traced function (the round
engine's ``shard_train`` -> ``one_client`` -> closure tower). This is
lexical reachability, not a call graph: a helper called from traced
code but defined at module scope and never registered with a transform
is NOT scanned — the factory idiom this codebase uses everywhere
(make_train_fn closures) keeps traced code lexically nested, which is
exactly what makes the lexical rule strong here.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Set, Tuple

from commefficient_tpu.analysis.engine import Violation

# ---------------------------------------------------------------------------
# shared AST helpers


def _dotted(node: ast.AST) -> Optional[str]:
    """Dotted source name of a Name/Attribute chain ('jax.random.split'),
    or None when the expression is not a plain chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _terminal(name: Optional[str]) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


class ModuleInfo:
    """One parsed file plus the derived facts every rule shares: parent
    links, the set of traced function/lambda nodes, and source text."""

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.tree = tree
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.traced_roots = _find_traced_roots(tree)

    def enclosing_functions(self, node: ast.AST) -> Iterator[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                yield cur
            cur = self.parents.get(cur)

    def in_traced(self, node: ast.AST) -> bool:
        """True when `node` sits lexically inside a traced function."""
        if node in self.traced_roots:
            return True
        return any(f in self.traced_roots
                   for f in self.enclosing_functions(node))

    def segment(self, node: ast.AST) -> str:
        try:
            return ast.get_source_segment(self.source, node) or ""
        except Exception:  # graftlint: disable=GL005 -- best-effort source echo
            return ""


# transform entry points whose function-valued arguments become traced
# (pallas_call included: a Pallas kernel body is traced code — the
# same host-sync/control-flow hazards apply inside it, plus Mosaic's
# own restrictions)
_TRACE_ENTRY_CALLS = frozenset({
    "jit", "pmap", "vmap", "grad", "value_and_grad", "scan", "cond",
    "while_loop", "fori_loop", "switch", "shard_map", "checkpoint",
    "remat", "associative_scan", "custom_vjp", "custom_jvp",
    "pallas_call",
})
_TRACE_DECORATORS = frozenset({
    "jit", "pmap", "vmap", "shard_map", "checkpoint", "remat",
    "custom_vjp", "custom_jvp",
})


def _decorator_marks_traced(dec: ast.expr) -> bool:
    name = _terminal(_dotted(dec))
    if name in _TRACE_DECORATORS:
        return True
    if isinstance(dec, ast.Call):
        if _terminal(_dotted(dec.func)) in _TRACE_DECORATORS:
            return True
        # @partial(jax.jit, static_argnums=...) and friends
        if _terminal(_dotted(dec.func)) == "partial":
            return any(_terminal(_dotted(a)) in _TRACE_DECORATORS
                       for a in dec.args)
    return False


def _find_traced_roots(tree: ast.Module) -> Set[ast.AST]:
    by_name: Dict[str, List[ast.AST]] = {}
    roots: Set[ast.AST] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
            if any(_decorator_marks_traced(d) for d in node.decorator_list):
                roots.add(node)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _terminal(_dotted(node.func)) not in _TRACE_ENTRY_CALLS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Lambda):
                roots.add(arg)
            name = _dotted(arg)
            if name and "." not in name:
                roots.update(by_name.get(name, ()))
    return roots


def _walk_traced(module: ModuleInfo) -> Iterator[ast.AST]:
    """Every node lexically inside a traced root, visited once."""
    seen: Set[ast.AST] = set()
    for root in module.traced_roots:
        body = root.body if isinstance(root.body, list) else [root.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if node not in seen:
                    seen.add(node)
                    yield node


# ---------------------------------------------------------------------------
# GL001 — host nondeterminism reachable from traced code

_GL001_CLOCKS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.perf_counter",
    "datetime.now", "datetime.utcnow", "datetime.today",
    "datetime.datetime.now", "datetime.datetime.utcnow",
})
_NP_GLOBAL_DRAWS = frozenset({
    "rand", "randn", "random", "random_sample", "randint", "choice",
    "permutation", "shuffle", "uniform", "normal", "standard_normal",
    "beta", "binomial", "poisson", "exponential", "bytes",
})
_PY_RANDOM_DRAWS = frozenset({
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "uniform", "gauss", "sample", "betavariate", "getrandbits",
})


def check_gl001(module: ModuleInfo) -> Iterator[Violation]:
    for node in _walk_traced(module):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if not name:
            continue
        what = None
        if name in _GL001_CLOCKS or name.endswith(".datetime.now"):
            what = f"host clock `{name}()`"
        elif (name.startswith(("np.random.", "numpy.random."))
              and _terminal(name) in _NP_GLOBAL_DRAWS):
            what = f"unseeded global-state draw `{name}()`"
        elif (name.startswith("random.")
              and _terminal(name) in _PY_RANDOM_DRAWS):
            what = f"unseeded `{name}()`"
        if what:
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL001",
                f"{what} inside traced code: the value freezes at trace "
                "time (or retraces nondeterministically), breaking the "
                "pure-(state, seed, round) round contract; thread a "
                "seeded generator / jax.random key in as data")


# ---------------------------------------------------------------------------
# GL002 — hidden host syncs / trace breaks in traced code

_NP_ALLOWED = frozenset({
    # dtype constructors and shape introspection are trace-safe
    "float16", "float32", "float64", "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64", "bool_", "dtype", "ndim",
    "shape", "isscalar", "broadcast_shapes",
})


def check_gl002(module: ModuleInfo) -> Iterator[Violation]:
    for node in _walk_traced(module):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name and name.startswith(("np.", "numpy.")):
            if name.startswith(("np.random.", "numpy.random.")):
                continue  # GL001's domain
            if _terminal(name) not in _NP_ALLOWED:
                yield Violation(
                    module.path, node.lineno, node.col_offset, "GL002",
                    f"raw numpy call `{name}(...)` inside traced code: "
                    "on a traced value this breaks the trace (or "
                    "silently bakes in a host constant) and forces a "
                    "device->host sync; use jnp/lax")
            continue
        if name in ("jax.device_get", "device_get"):
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL002",
                "`jax.device_get` inside traced code is a host sync; "
                "return the value and materialize it outside the "
                "traced function")
            continue
        if (isinstance(node.func, ast.Attribute) and node.func.attr == "item"
                and not node.args and not node.keywords):
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL002",
                "`.item()` inside traced code is a trace break / host "
                "sync; keep the value as an array")
            continue
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int", "bool")
                and len(node.args) == 1
                and isinstance(node.args[0], (ast.Call, ast.Subscript))):
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL002",
                f"`{node.func.id}(...)` of a computed value inside "
                "traced code concretizes a tracer (host sync / "
                "ConcretizationTypeError); keep it as an array or hoist "
                "it out of the traced function")


# ---------------------------------------------------------------------------
# GL003 — PRNG key reuse across draws

_KEY_NONDRAWS = frozenset({
    "PRNGKey", "key", "split", "fold_in", "key_data", "wrap_key_data",
    "key_impl", "clone",
})


def _jax_random_aliases(tree: ast.Module) -> Set[str]:
    """Local names that refer to the jax.random module: 'jax.random'
    always; plus whatever `from jax import random [as r]` / `import
    jax.random as jr` bind. Plain `import random` (stdlib) never
    qualifies, so stdlib draws don't masquerade as key consumption."""
    aliases = {"jax.random"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for a in node.names:
                if a.name == "random":
                    aliases.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.random" and a.asname:
                    aliases.add(a.asname)
    return aliases


def _is_jax_random(name: Optional[str], aliases: Set[str]) -> bool:
    if not name or "." not in name:
        return False
    return name.rsplit(".", 1)[0] in aliases


def check_gl003(module: ModuleInfo) -> Iterator[Violation]:
    aliases = _jax_random_aliases(module.tree)
    funcs = [n for n in ast.walk(module.tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def _owner(node: ast.AST) -> Optional[ast.AST]:
        """The nearest enclosing def, looking THROUGH lambdas (they
        cannot rebind names, and a draw inside `vmap(lambda i: ...)`
        genuinely consumes the enclosing scope's key)."""
        for f in module.enclosing_functions(node):
            if not isinstance(f, ast.Lambda):
                return f
        return None

    for fn in funcs:
        # Per-scope linear scan: only nodes whose owning def is `fn`
        # participate — a nested def is a separate binding scope (its
        # assignments must not clear the outer drawn set, and it gets
        # its own pass from the `funcs` list). Cross-scope reuse
        # (outer draw + closure draw on the same outer key) is out of
        # scope for this rule — precision over recall.
        # events in source order: (lineno, col, kind, varname)
        events: List[Tuple[int, int, str, str]] = []
        for node in ast.walk(fn):
            if node is fn or _owner(node) is not fn:
                continue
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    for name_node in ast.walk(tgt):
                        if isinstance(name_node, ast.Name):
                            events.append((node.lineno, node.col_offset,
                                           "assign", name_node.id))
            elif isinstance(node, ast.Call):
                name = _dotted(node.func)
                if not _is_jax_random(name, aliases):
                    continue
                if _terminal(name) in _KEY_NONDRAWS:
                    continue
                # a draw: jax.random.normal(key, ...) — first positional
                # arg (or key=...) names the consumed key
                key_arg = node.args[0] if node.args else next(
                    (kw.value for kw in node.keywords
                     if kw.arg == "key"), None)
                if isinstance(key_arg, ast.Name):
                    events.append((node.lineno, node.col_offset,
                                   "draw", key_arg.id))
        drawn: Set[str] = set()
        for lineno, col, kind, name in sorted(events):
            if kind == "assign":
                drawn.discard(name)
            elif kind == "draw":
                if name in drawn:
                    yield Violation(
                        module.path, lineno, col, "GL003",
                        f"PRNG key `{name}` consumed by a second draw "
                        "without an intervening split/fold_in: the two "
                        "draws are perfectly correlated. fold_in a "
                        "distinct domain tag (the dropout-vs-straggler "
                        "discipline of utils/faults) or split the key")
                drawn.add(name)


# ---------------------------------------------------------------------------
# GL004 — Python control flow over traced values

_ARRAY_REDUCERS = frozenset({"any", "all", "sum", "mean", "max", "min",
                             "prod", "item"})


def _traced_value_expr(expr: ast.AST) -> Optional[str]:
    """A sub-expression that clearly produces a traced array value:
    a jnp./jax.numpy./jax.lax. call, or an array-reducer method call."""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name and name.startswith(("jnp.", "jax.numpy.", "jax.lax.",
                                     "lax.")):
            return name
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _ARRAY_REDUCERS
                and not node.args):
            base = _dotted(node.func.value)
            # cfg.*, self.* etc. are host objects; bare names and
            # computed bases are the array case
            if base is None or "." not in base:
                return f".{node.func.attr}()"
    return None


def check_gl004(module: ModuleInfo) -> Iterator[Violation]:
    for node in _walk_traced(module):
        if isinstance(node, (ast.If, ast.While)):
            hit = _traced_value_expr(node.test)
            if hit:
                kind = "if" if isinstance(node, ast.If) else "while"
                yield Violation(
                    module.path, node.lineno, node.col_offset, "GL004",
                    f"Python `{kind}` over a traced value ({hit}): this "
                    "forces a trace-time concretization (or a silent "
                    "per-value retrace); use lax.cond / lax.select / "
                    "jnp.where" + (" / lax.while_loop"
                                   if kind == "while" else ""))
        elif isinstance(node, ast.For):
            hit = _traced_value_expr(node.iter)
            if hit:
                yield Violation(
                    module.path, node.lineno, node.col_offset, "GL004",
                    f"Python `for` over a traced value ({hit}): the loop "
                    "unrolls at trace time (program size scales with "
                    "the array) or fails to concretize; use lax.scan / "
                    "lax.fori_loop")
        elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "bool" and len(node.args) == 1
                and _traced_value_expr(node.args[0])):
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL004",
                "`bool(...)` of a traced value concretizes the tracer; "
                "use lax.cond / jnp.where")


# ---------------------------------------------------------------------------
# GL005 — fault-swallowing broad except handlers

_BROAD = frozenset({"Exception", "BaseException"})


def _names_broad(type_expr: Optional[ast.expr]) -> bool:
    if type_expr is None:
        return True  # bare `except:`
    if isinstance(type_expr, ast.Tuple):
        return any(_names_broad(e) for e in type_expr.elts)
    return _terminal(_dotted(type_expr)) in _BROAD


def _reraises(handler: ast.ExceptHandler) -> bool:
    """True when the handler body contains a bare `raise` (re-raise) at
    any depth — the sanctioned cleanup-then-reraise and
    classify-then-reraise idioms (multihost.initialize, utils/retry)."""
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise) and node.exc is None:
            return True
    return False


def check_gl005(module: ModuleInfo) -> Iterator[Violation]:
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if _names_broad(node.type) and not _reraises(node):
            caught = (module.segment(node.type) if node.type is not None
                      else "<bare>")
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL005",
                f"broad `except {caught}` without re-raise would swallow "
                "InjectedFault and defeat the fault harness (and mask "
                "real config errors as transients); catch the specific "
                "expected exceptions, or re-raise")


# ---------------------------------------------------------------------------
# GL006 — non-atomic file writes

_WRITE_MODES = ("w", "a", "x", "+")


def _enclosing_scope_calls_replace(module: ModuleInfo,
                                   node: ast.AST) -> bool:
    scope: ast.AST = module.tree
    for fn in module.enclosing_functions(node):
        scope = fn
        break
    for n in ast.walk(scope):
        if isinstance(n, ast.Call) and _dotted(n.func) in (
                "os.replace", "os.rename"):
            return True
    return False


def _mentions_tmp(module: ModuleInfo, expr: ast.AST) -> bool:
    return "tmp" in module.segment(expr).lower()


def check_gl006(module: ModuleInfo) -> Iterator[Violation]:
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name in ("open", "io.open") or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "open" and name is None):
            mode = None
            if len(node.args) >= 2:
                mode = node.args[1]
            else:
                mode = next((kw.value for kw in node.keywords
                             if kw.arg == "mode"), None)
            if not (isinstance(mode, ast.Constant)
                    and isinstance(mode.value, str)
                    and any(ch in mode.value for ch in _WRITE_MODES)):
                continue
            target = node.args[0] if node.args else None
            if target is None or _mentions_tmp(module, target):
                continue
            if _enclosing_scope_calls_replace(module, node):
                continue
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL006",
                "open-for-write without the atomic `.tmp` + os.replace "
                "pattern (utils/atomic_io): a preemption mid-write "
                "corrupts the previous file in place; write to "
                "`<path>.tmp` and os.replace, or use "
                "atomic_write_text/atomic_savez")
        elif name in ("np.save", "np.savez", "np.savez_compressed",
                      "numpy.save", "numpy.savez",
                      "numpy.savez_compressed"):
            target = node.args[0] if node.args else None
            # a bare Name is typically an open file handle (already
            # routed through the atomic open) or a precomputed tmp path
            if target is None or isinstance(target, ast.Name):
                continue
            if _mentions_tmp(module, target):
                continue
            if _enclosing_scope_calls_replace(module, node):
                continue
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL006",
                f"`{name}` straight to its destination path: a "
                "preemption mid-serialize leaves a torn archive under "
                "the real name; use utils/atomic_io.atomic_savez")


# ---------------------------------------------------------------------------
# GL007 — unconstrained shard_map/pjit output layouts

# transform -> (keyword that pins its output layout, positional arg
# count that reaches the same slot: shard_map(f, mesh, in_specs,
# out_specs) and pjit(f, in_shardings, out_shardings) are both legal
# positional forms)
_GL007_CALLS = {"shard_map": ("out_specs", 4),
                "pjit": ("out_shardings", 3)}


def check_gl007(module: ModuleInfo) -> Iterator[Violation]:
    """A `shard_map(...)` / `pjit(...)` call without an explicit
    `out_specs` / `out_shardings` leaves the output layout to GSPMD's
    propagation: on a partially-manual mesh (the engine's clients-
    manual / model-auto layout) that silently inserts reshards on new
    outputs instead of failing — the layout bug class the PR-3
    ROADMAP opening named. Mechanical and precise: only the literal
    call sites are checked; a call forwarding **kwargs, or passing
    enough positional args to cover the out-spec slot, is left alone
    (the spec may ride there), matching the lint's precision-over-
    recall rule."""
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        term = _terminal(_dotted(node.func))
        spec = _GL007_CALLS.get(term)
        if spec is None:
            continue
        kwname, pos_count = spec
        if any(kw.arg is None for kw in node.keywords):
            continue  # **kwargs forwarding: can't see the spec
        if (len(node.args) >= pos_count
                or any(isinstance(a, ast.Starred) for a in node.args)):
            continue  # positional form (or *args) covers the slot
        kw = next((kw.value for kw in node.keywords
                   if kw.arg == kwname), None)
        if kw is None or (isinstance(kw, ast.Constant)
                          and kw.value is None):
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL007",
                f"`{term}(...)` without an explicit `{kwname}`: the "
                "output layout is left to GSPMD propagation, which "
                "silently reshards new outputs on partially-manual "
                f"meshes; pass `{kwname}` (or pin each output with "
                "with_sharding_constraint before returning)")


# ---------------------------------------------------------------------------
# GL008 — exact large-k top-k inside traced code

# Exact `lax.top_k` lowers to a sorting network on TPU whose cost
# grows with k * d — the ~125 ms/round regression class PERF.md §1
# measured at k=50k (vs ~1 ms for the approx_max_k partial reduce).
# Flag only a STATIC k at or above this bound: small-k exact top-k is
# fine (and is what approx_max_k itself degenerates to), and a
# non-constant k is invisible to a syntactic rule (precision over
# recall, like every rule here).
GL008_MIN_K = 2048


def check_gl008(module: ModuleInfo) -> Iterator[Violation]:
    for node in _walk_traced(module):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if _terminal(name) != "top_k" or not name:
            continue
        # jax.lax.top_k / lax.top_k; jnp has no top_k, and a bare or
        # differently-rooted `top_k` is someone else's function
        root = name.rsplit(".", 1)[0]
        if root not in ("lax", "jax.lax"):
            continue
        k_arg = node.args[1] if len(node.args) >= 2 else next(
            (kw.value for kw in node.keywords if kw.arg == "k"), None)
        if not (isinstance(k_arg, ast.Constant)
                and isinstance(k_arg.value, int)
                and k_arg.value >= GL008_MIN_K):
            continue
        yield Violation(
            module.path, node.lineno, node.col_offset, "GL008",
            f"exact `{name}` with static k={k_arg.value} inside traced "
            "code: exact top-k lowers to a full sorting network on TPU "
            "(the ~125 ms/round regression class in PERF.md); use "
            "`jax.lax.approx_max_k` (error feedback absorbs the ~5% "
            "recall miss) or the sampled-threshold mask "
            "(ops/flat.sampled_threshold_mask)")


# ---------------------------------------------------------------------------
# GL015 — strided subscripts in the traced packages

# On jax 0.9.0 a `jnp` subscript with a step (`x[::n]`, `x[a:b:n]`)
# does not trace to a strided slice: it traces to iota -> mul ->
# `gather`, one index lookup per element kept, and under a `vmap`
# the chip's compiler runs it that way (ops/flat's top-k sample,
# `sq[::6]` at [16, 6568640]: 1,094,774 lookups, 17.6 ms of a 64.5 ms
# round on a v5e; PERF.md section 6, PR 34). `jax.lax.slice(x, start,
# limit, strides)` is one `slice` instruction over the same elements.
# Path-scoped like GL010, to the packages whose arrays are traced
# (ops/, federated/, compress/), and over the whole file: their
# kernels are module-level functions no transform names in the same
# file, so lexical tracedness would miss exactly the site that cost.
# A literal step of 1 (no op) or -1 (`rev`) is left alone; a host-side
# numpy stride in these packages takes a `# graftlint: disable=GL015`.
_GL015_SCOPES = ("/ops/", "/federated/", "/compress/")


def check_gl015(module: ModuleInfo) -> Iterator[Violation]:
    path = "/" + module.path.replace(os.sep, "/")
    if not any(scope in path for scope in _GL015_SCOPES):
        return
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Subscript):
            continue
        dims = (node.slice.elts if isinstance(node.slice, ast.Tuple)
                else [node.slice])
        for dim in dims:
            if not isinstance(dim, ast.Slice) or dim.step is None:
                continue
            try:
                if ast.literal_eval(dim.step) in (1, -1):
                    continue
            except ValueError:
                pass    # a computed step: not a literal
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL015",
                f"subscript with a step `{module.segment(node)}`: on "
                "jax 0.9.0 a strided `jnp` index traces to iota -> "
                "`gather` (one lookup per element kept; 17.6 ms of a "
                "64.5 ms round at ops/flat's top-k sample), not to a "
                "strided slice; use `jax.lax.slice(x, start, limit, "
                "strides)`")


# ---------------------------------------------------------------------------
# GL009 — PRNG-domain constants outside the central registry

# The engine's deterministic-replay story separates the dropout /
# straggler / scheduler streams by counter-based domain tags. Those
# tags live in analysis/domains.DOMAINS — the ONE place uniqueness is
# asserted. This rule holds the line syntactically: an inline hex
# literal fed to `fold_in` / `SeedSequence` is a domain tag that
# bypassed the registry (invisible to the collision assert), and a
# duplicate value inside the registry dict itself is a collision. Both
# apply file-wide, not just in traced scope: the production draws
# (utils/faults, scheduler/policy) are deliberately host-side.

_GL009_SINKS = frozenset({"fold_in", "SeedSequence"})
_GL009_REGISTRY_SUFFIX = "analysis/domains.py"


def _is_hex_literal(module: ModuleInfo, node: ast.AST) -> bool:
    if not (isinstance(node, ast.Constant) and isinstance(node.value, int)
            and not isinstance(node.value, bool)):
        return False
    return module.segment(node).strip().lower().startswith("0x")


def check_gl009(module: ModuleInfo) -> Iterator[Violation]:
    # (a) inline hex domain tags at a key-derivation sink, at any
    # argument depth (SeedSequence takes its entropy as a list)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if _terminal(_dotted(node.func)) not in _GL009_SINKS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if _is_hex_literal(module, sub):
                    yield Violation(
                        module.path, sub.lineno, sub.col_offset, "GL009",
                        f"inline hex domain tag `{module.segment(sub)}` "
                        "in a PRNG key derivation: domain constants "
                        "must come from analysis/domains.DOMAINS (the "
                        "registry asserts stream uniqueness; an inline "
                        "tag can silently collide with an existing "
                        "stream)")
    # (b) collisions inside the registry itself (pure AST — graftlint
    # never executes the tree, so the import-time assert is re-proven
    # syntactically on the literal dict)
    if not module.path.replace(os.sep, "/").endswith(
            _GL009_REGISTRY_SUFFIX):
        return
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "DOMAINS"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            continue
        seen: Dict[int, str] = {}
        for k, v in zip(node.value.keys, node.value.values):
            if not (isinstance(v, ast.Constant)
                    and isinstance(v.value, int)):
                continue
            name = (k.value if isinstance(k, ast.Constant) else
                    module.segment(k))
            if v.value in seen:
                yield Violation(
                    module.path, v.lineno, v.col_offset, "GL009",
                    f"PRNG domain collision: {name!r} reuses tag "
                    f"{hex(v.value)} already registered to "
                    f"{seen[v.value]!r} — correlated streams break the "
                    "independent-failure-process model")
            else:
                seen[v.value] = name


# ---------------------------------------------------------------------------
# GL010 — mesh-axis names outside the central registry

# The sharding layer's axis names live in analysis/domains.MESH_AXES
# (`clients`, `model`) — the one place a reviewer audits the mesh
# layout, mirroring the GL009 PRNG-domain discipline. This rule holds
# the line syntactically in the two packages that construct shardings
# (parallel/, federated/): a string literal at an axis-name position —
# a PartitionSpec/P argument, a Mesh axis_names entry, a shard_map
# axis_names member, a psum-family axis argument — that is not a
# registered MESH_AXES value is a typo or an unregistered axis, either
# of which GSPMD would silently absorb as a fully-replicated spec
# (the graftmesh AU007 failure class, caught here before a trace is
# ever needed). Literals that ARE registry values are fine: the rule
# checks by value, so P("clients") and P(CLIENTS_AXIS) are equally
# clean — migration to the constants is hygiene, not a lint gate.

from commefficient_tpu.analysis.domains import MESH_AXES  # noqa: E402

_GL010_SCOPES = ("/parallel/", "/federated/")
# call terminal -> how to find axis-name strings: "args" scans every
# positional/keyword argument expression for string constants;
# "mesh_ctor" scans the axis_names kwarg plus its positional slot
# (Mesh(devs, ("clients",))); "kwarg_only" scans only the kwarg
# (shard_map's positional slot 1 is the MESH argument, whose
# expression may legitimately contain unrelated strings)
_GL010_SINKS = {
    "PartitionSpec": "args",
    "P": "args",
    "Mesh": "mesh_ctor",
    "shard_map": "kwarg_only",
    "psum": "axis_arg",
    "pmax": "axis_arg",
    "pmin": "axis_arg",
    "all_gather": "axis_arg",
    "pbroadcast": "axis_arg",
    "pcast": "axis_arg",
}


def _string_constants(expr: ast.AST) -> Iterator[ast.Constant]:
    for node in ast.walk(expr):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node


def check_gl010(module: ModuleInfo) -> Iterator[Violation]:
    path = "/" + module.path.replace(os.sep, "/")
    if not any(scope in path for scope in _GL010_SCOPES):
        return
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        sink = _GL010_SINKS.get(_terminal(_dotted(node.func)))
        if sink is None:
            continue
        if sink == "args":
            exprs = list(node.args) + [kw.value for kw in node.keywords]
        elif sink == "axis_arg":
            # psum(x, "clients") / all_gather(x, "clients", ...) /
            # pcast(x, "clients", to=...): the axis rides the second
            # positional slot or an axis_name(s) kwarg
            exprs = node.args[1:2] + [kw.value for kw in node.keywords
                                      if kw.arg in ("axis_name",
                                                    "axis_names")]
        else:
            # Mesh(devs, axis_names) / Mesh(devs, axis_names=...) —
            # positional slot only for the constructor, where slot 1
            # IS the axis tuple
            exprs = node.args[1:2] if sink == "mesh_ctor" else []
            exprs += [kw.value for kw in node.keywords
                      if kw.arg == "axis_names"]
        for expr in exprs:
            for const in _string_constants(expr):
                if const.value in MESH_AXES:
                    continue
                yield Violation(
                    module.path, const.lineno, const.col_offset,
                    "GL010",
                    f"axis name {const.value!r} in a sharding "
                    "construction is not in the mesh-axis registry "
                    f"(analysis/domains.MESH_AXES = {MESH_AXES}): a "
                    "typo or unregistered axis becomes a silently "
                    "replicated spec under GSPMD propagation — use a "
                    "registered axis (or register the new one)")


# ---------------------------------------------------------------------------
# GL011 — wall-clock deltas used as durations

# A difference of two time.time() readings is NOT a duration: the wall
# clock steps under NTP correction (and jumps at DST/admin changes),
# so a duration derived from it can come out negative or wildly wrong
# exactly when a long production run crosses a correction — the hazard
# class graftscope (ISSUE 13) exists to measure AROUND. Durations must
# come from time.monotonic()/time.perf_counter(); wall time is for
# timestamps and cross-machine correlation only (the journal records
# both: `ts` wall, `mono` monotonic). The rule is syntactic + local:
# it flags a subtraction where BOTH operands are wall-clock-derived —
# a direct time.time()/time.time_ns() call, or a local name assigned
# from one in the same function scope. Comparing time.time() against
# an offset or a file mtime (checkpoint age GC) subtracts a
# NON-clock operand and is legitimately wall-clock — not flagged.

_GL011_WALL_CALLS = frozenset({"time.time", "time.time_ns"})


def _is_wall_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and (_dotted(node.func) or "") in _GL011_WALL_CALLS)


def _gl011_scopes(tree: ast.Module) -> Iterator[ast.AST]:
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _gl011_scope_nodes(scope: ast.AST) -> Iterator[ast.AST]:
    """Nodes lexically in `scope` ITSELF — nested function bodies are
    pruned (each is its own GL011 scope: a name bound from
    time.time() in one function must not taint the same name used as
    an ordinary parameter in another)."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def check_gl011(module: ModuleInfo) -> Iterator[Violation]:
    seen: Set[Tuple[int, int]] = set()
    for scope in _gl011_scopes(module.tree):
        # names bound DIRECTLY from a wall-clock call in this scope
        wall_names: Set[str] = set()
        for node in _gl011_scope_nodes(scope):
            if (isinstance(node, ast.Assign)
                    and _is_wall_call(node.value)):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        wall_names.add(tgt.id)
            elif (isinstance(node, ast.AnnAssign)
                    and node.value is not None
                    and _is_wall_call(node.value)
                    and isinstance(node.target, ast.Name)):
                wall_names.add(node.target.id)

        def _wall_derived(expr: ast.AST) -> Optional[str]:
            if _is_wall_call(expr):
                return f"{_dotted(expr.func)}()"
            if isinstance(expr, ast.Name) and expr.id in wall_names:
                return f"`{expr.id}` (assigned from time.time())"
            return None

        for node in _gl011_scope_nodes(scope):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)):
                continue
            key = (node.lineno, node.col_offset)
            if key in seen:
                continue
            left = _wall_derived(node.left)
            right = _wall_derived(node.right)
            if left is None or right is None:
                continue
            seen.add(key)
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL011",
                f"wall-clock delta used as a duration: {left} - "
                f"{right}. time.time() steps under NTP correction, "
                "so its differences are not durations — use "
                "time.monotonic()/time.perf_counter() for intervals "
                "(keep time.time() for timestamps and comparisons "
                "against external wall-clock values like file "
                "mtimes)")


# ---------------------------------------------------------------------------
# GL012 — anonymous writer threads

# graftscope (telemetry/trace) stitches writer spans into Perfetto
# rows BY THREAD NAME, and the journal's trace records carry the
# thread name as the correlation key. An anonymous thread gets the
# interpreter's `Thread-N` counter name, which differs across
# restarts (and between two writers started in a different order), so
# a resumed run's spans land on a DIFFERENT Perfetto row than the
# crashed run's — the cross-restart timeline graftscope exists for
# silently splits. Mechanical and precise: every
# `threading.Thread(...)` construction must pass an explicit `name=`
# (the journal's "journal-writer", the checkpoint writer's
# f"{name}-writer"); **kwargs forwarding is left alone (the name may
# ride there).


def check_gl012(module: ModuleInfo) -> Iterator[Violation]:
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if _dotted(node.func) not in ("threading.Thread", "Thread"):
            continue
        if any(kw.arg is None for kw in node.keywords):
            continue  # **kwargs forwarding: can't see the name
        if any(kw.arg == "name" for kw in node.keywords):
            continue
        if (len(node.args) >= 3
                or any(isinstance(a, ast.Starred) for a in node.args)):
            continue  # Thread(group, target, name, ...): the third
            # positional slot IS the name (or *args may cover it)
        yield Violation(
            module.path, node.lineno, node.col_offset, "GL012",
            "`threading.Thread(...)` without an explicit `name=`: the "
            "interpreter's Thread-N fallback differs across restarts, "
            "so graftscope's thread-keyed trace rows (and the "
            "watchdog's writer-naming) break across a resume; name "
            "the thread after its role (journal-writer, "
            "state-spill-writer)")


# ---------------------------------------------------------------------------
# GL013 — float equality comparison on traced values (ISSUE 18)

# The crash->resume contract (graftnum NU004) makes BIT-exactness the
# replay guarantee, and FetchSGD's error feedback leans on one legal
# float-equality idiom: comparison against EXACT ZERO (`update == 0`,
# `vals == 0.0`) — a coordinate is either untouched or was assigned
# 0.0 through a `where`, so the test is a bit test, not an
# approximation. Every OTHER float equality in traced code is a
# rounding hazard: `x == 0.95` is False for the nearest f32 to 0.95
# after one ulp of drift, and `computed == computed'` couples program
# logic to reassociation order (exactly what graftnum's NU005 ulp
# bound prices as nonzero). The rule is AST-level and so heuristic:
# it flags equality against a non-zero FLOAT literal, and equality
# where a side is a clearly-traced jnp/lax expression — int-literal
# comparisons (ids, chunk indices) and bare-name pairs stay quiet.


def _zero_literal(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, (int, float))
            and not isinstance(node.value, bool)
            and float(node.value) == 0.0)


def check_gl013(module: ModuleInfo) -> Iterator[Violation]:
    for node in _walk_traced(module):
        if not isinstance(node, ast.Compare):
            continue
        if not all(isinstance(op, (ast.Eq, ast.NotEq))
                   for op in node.ops):
            continue
        sides = [node.left] + list(node.comparators)
        if any(_zero_literal(s) for s in sides):
            # the sanctioned sparsity/sentinel bit test (`update ==
            # 0` error-feedback masking, `vals == 0.0` unfilled-slot
            # sentinel): exact by construction, replay-stable
            continue
        float_lit = next(
            (s.value for s in sides
             if isinstance(s, ast.Constant)
             and isinstance(s.value, float)), None)
        if float_lit is not None:
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL013",
                f"float equality against {float_lit!r} in traced "
                "code: one ulp of drift (psum reassociation, a "
                "backend change) flips this comparison, breaking the "
                "crash->resume bit-exactness contract — compare "
                "against exact 0 (the sparsity idiom), use an "
                "inequality threshold, or jnp.isclose with an "
                "explicit tolerance")
            continue
        hit = next((h for h in map(_traced_value_expr, sides) if h),
                   None)
        if hit is not None:
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL013",
                f"float `==`/`!=` on a computed traced value ({hit}): "
                "equality between computed floats couples logic to "
                "summation/reassociation order (graftnum prices that "
                "drift as a nonzero ulp bound) — compare against "
                "exact 0, use an inequality threshold, or "
                "jnp.isclose with an explicit tolerance")


# ---------------------------------------------------------------------------
# GL014 — controller plan wire fields outside the central registry

# The control/ subsystem (ISSUE 20) rides every controller's adjusted
# value on a named RoundPlan wire field; the journaled plan stream is
# the authoritative adjustment log a coordinator takeover replays.
# Those fields live in analysis/domains.CONTROL_FIELDS — the one place
# uniqueness is asserted — because two controllers sharing a field
# silently overwrite each other's wire decisions (invisible at
# runtime, catastrophic on a resume). This rule holds the line
# syntactically, mirroring GL009: (a) a `WIRE_FIELD = "..."` class
# attribute anywhere in the tree whose string literal is not a
# registered CONTROL_FIELDS value is a controller that bypassed the
# registry; (b) a duplicate value inside the registry dict itself is a
# collision, re-proven pure-AST on the literal dict.

from commefficient_tpu.analysis.domains import CONTROL_FIELDS  # noqa: E402

_GL014_ATTR = "WIRE_FIELD"
_GL014_REGISTRY_SUFFIX = "analysis/domains.py"


def check_gl014(module: ModuleInfo) -> Iterator[Violation]:
    # (a) unregistered WIRE_FIELD class attributes, tree-wide: the
    # attribute name is the control/ base-class contract, so any
    # assignment to it claims a wire field
    registered = set(CONTROL_FIELDS.values())
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == _GL014_ATTR
                        for t in node.targets)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            continue
        field = node.value.value
        if field and field not in registered:
            yield Violation(
                module.path, node.lineno, node.col_offset, "GL014",
                f"controller wire field {field!r} is not registered "
                "in analysis/domains.CONTROL_FIELDS: the registry is "
                "where wire-field uniqueness is asserted — an "
                "unregistered field can silently collide with an "
                "existing controller's journaled plan stream")
    # (b) collisions inside the registry itself (pure AST — the
    # import-time assert re-proven syntactically on the literal dict)
    if not module.path.replace(os.sep, "/").endswith(
            _GL014_REGISTRY_SUFFIX):
        return
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id == "CONTROL_FIELDS"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            continue
        seen: Dict[str, str] = {}
        for k, v in zip(node.value.keys, node.value.values):
            if not (isinstance(v, ast.Constant)
                    and isinstance(v.value, str)):
                continue
            name = (k.value if isinstance(k, ast.Constant) else
                    module.segment(k))
            if v.value in seen:
                yield Violation(
                    module.path, v.lineno, v.col_offset, "GL014",
                    f"controller wire-field collision: {name!r} "
                    f"reuses field {v.value!r} already registered to "
                    f"{seen[v.value]!r} — two controllers on one wire "
                    "field overwrite each other's plan-carried "
                    "adjustments")
            else:
                seen[v.value] = name


# ---------------------------------------------------------------------------

ALL_RULES = {
    "GL001": check_gl001,
    "GL002": check_gl002,
    "GL003": check_gl003,
    "GL004": check_gl004,
    "GL005": check_gl005,
    "GL006": check_gl006,
    "GL007": check_gl007,
    "GL008": check_gl008,
    "GL009": check_gl009,
    "GL010": check_gl010,
    "GL011": check_gl011,
    "GL012": check_gl012,
    "GL013": check_gl013,
    "GL014": check_gl014,
    "GL015": check_gl015,
}

RULE_DOCS = {
    "GL001": "host nondeterminism (clocks, unseeded global RNG) inside "
             "traced code",
    "GL002": "raw numpy / .item() / device_get inside traced code "
             "(hidden sync, trace break)",
    "GL003": "PRNG key consumed by two draws without split/fold_in "
             "domain separation",
    "GL004": "Python if/while/for over traced values where "
             "lax.cond/scan is required",
    "GL005": "broad except handler that would swallow InjectedFault "
             "(no re-raise)",
    "GL006": "file write without the atomic .tmp + os.replace pattern",
    "GL007": "shard_map/pjit output layout left unconstrained (no "
             "out_specs/out_shardings, no with_sharding_constraint)",
    "GL008": "exact lax.top_k with large static k in traced code "
             "(TPU sorting-network cliff; use approx_max_k or the "
             "fused selection kernel)",
    "GL009": "PRNG domain tag outside the analysis/domains registry "
             "(inline hex in fold_in/SeedSequence, or a registry "
             "collision)",
    "GL010": "mesh-axis name in a sharding construction (parallel/, "
             "federated/) outside the analysis/domains MESH_AXES "
             "registry",
    "GL011": "wall-clock delta (time.time() difference) used as a "
             "duration — NTP steps corrupt it; use "
             "time.monotonic()/perf_counter for intervals",
    "GL012": "threading.Thread constructed without an explicit name= "
             "(anonymous Thread-N names break graftscope's "
             "thread-keyed trace rows across restarts)",
    "GL013": "float ==/!= on traced values (non-zero literal or "
             "computed comparand) — one ulp of reassociation drift "
             "flips it; exact-zero sparsity tests stay legal",
    "GL014": "controller plan wire field outside the analysis/domains "
             "CONTROL_FIELDS registry (unregistered WIRE_FIELD class "
             "attribute, or a registry collision)",
    "GL015": "subscript with a step (x[::n]) in ops/, federated/ or "
             "compress/ — jax 0.9.0 traces it to a gather, not a "
             "strided slice; use jax.lax.slice",
}
