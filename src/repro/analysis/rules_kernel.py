"""R4 — kernel-contract checks for ``pl.pallas_call`` sites.

Pallas contracts are easy to break silently: a BlockSpec ``index_map``
with the wrong arity, a kernel body whose ref count no longer matches
``in_specs + out_specs + scratch_shapes``, an operand list out of step
with the specs — and, the seed-bug class, a *page walk* whose table
column is not clamped to the sequence's live pages, so the DMA reads a
stale physical block id and attends to garbage KV.

Everything here is abstract evaluation over the wrapper's AST with a
small constant environment (representative shapes for anything that
cannot be computed statically):

* ``kernel.index-map-arity`` — every ``index_map`` must take
  ``len(grid) + num_scalar_prefetch`` arguments (at most that many
  named ones when it takes ``*args``; at least ``len(grid)`` when
  ``num_scalar_prefetch`` is not a literal);
* ``kernel.body-arity`` — the kernel body's unbound positional params
  must equal prefetch + inputs + outputs + scratch (skipped for
  ``*refs`` bodies, non-literal spec lists and a non-literal
  ``num_scalar_prefetch``);
* ``kernel.operand-count`` — the immediate call must pass
  ``num_scalar_prefetch + len(in_specs)`` operands (same skips);
* ``kernel.page-walk-unbounded`` — every index map that subscripts a
  prefetched block table is evaluated over the full grid x a set of
  live lengths; each table column must stay within
  ``[0, max(ceil(live/block_size) - 1, 0)]`` and ``[0, table_width)``.
  Helper clamps (``_clamp_live``, ``_chunk_clamp``) are inlined.  The
  prefetch refs are the map's named params past the grid dims (a map
  may take the rest as ``*args``).  Only index maps are walked: a table
  walk that lives in the kernel body — the paged decode kernel's manual
  DMA loop over the live pages — is outside what this rule sees, and
  its bounds are held by the kernel tests instead;
* ``kernel.out-dtype`` — stores to the output ref must ``.astype`` the
  ref's dtype (f32 accumulators silently upcast the output otherwise).
"""
from __future__ import annotations

import ast
import itertools
import math
import re
from typing import Dict, List, Optional, Tuple

from repro.analysis.findings import Finding, finalize_occurrences
from repro.analysis.project import (FunctionInfo, Project, call_name,
                                    literal_or_none)

RULE = "R4"

# representative shape seeds: small enough to enumerate, chosen so every
# derived quantity (tiles, padded lengths) stays integral
_SEED_ENV = {"B": 2, "W": 8, "H": 2, "D": 4, "KV": 1, "G": 2, "MB": 5,
             "NB": 7, "BS": 4, "S": 8, "Sq": 8, "Sk": 8, "M": 8, "K": 32,
             "N": 8, "n_groups": 4}
# live-prefix lengths the page walk is exercised over (clipped to the
# pool capacity MB * BS below)
_LIVE_SET = (0, 1, 3, 4, 5, 9, 17, 20)
_GRID_CAP = 4096                        # skip walk on absurdly large grids
_OUT_REF_RE = re.compile(r"^(o|out)_ref$")


class _EvalError(Exception):
    pass


class _Table:
    """Abstract scalar-prefetch operand.

    * 2-index reads (``bt[b, col]``) are block-table lookups: the column
      is recorded for the bounds check and returned (the table value is
      unknown, only the column matters).
    * 1-index reads are scalar rows: ``sl[b]`` / ``info[0]`` give the
      live length; the literal index 1 (``info[1]`` = total_len) gives
      live + chunk width.
    """

    def __init__(self, live: int, total: int):
        self.live = live
        self.total = total
        self.cols: List[int] = []

    def read(self, idx_nodes: List[ast.expr], idx_vals: List[int]) -> int:
        if len(idx_vals) >= 2:
            col = int(idx_vals[1])
            self.cols.append(col)
            return col
        if len(idx_nodes) == 1 and isinstance(idx_nodes[0], ast.Constant) \
                and idx_nodes[0].value == 1:
            return self.total
        return self.live


class _Evaluator:
    """Tiny int evaluator over map/helper bodies."""

    def __init__(self, project: Project, module, env: Dict[str, object]):
        self.project = project
        self.module = module
        self.env = env

    def eval(self, node: ast.AST):
        if isinstance(node, ast.Constant):
            if isinstance(node.value, (int, float)):
                return node.value
            raise _EvalError(f"non-numeric constant {node.value!r}")
        if isinstance(node, ast.Name):
            if node.id in self.env:
                return self.env[node.id]
            raise _EvalError(f"unknown name {node.id}")
        if isinstance(node, ast.Tuple):
            return tuple(self.eval(e) for e in node.elts)
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand)
            if isinstance(node.op, ast.USub):
                return -v
            if isinstance(node.op, ast.UAdd):
                return +v
            raise _EvalError("unary op")
        if isinstance(node, ast.BinOp):
            le, r = self.eval(node.left), self.eval(node.right)
            op = node.op
            if isinstance(op, ast.Add):
                return le + r
            if isinstance(op, ast.Sub):
                return le - r
            if isinstance(op, ast.Mult):
                return le * r
            if isinstance(op, ast.FloorDiv):
                return le // r
            if isinstance(op, ast.Mod):
                return le % r
            if isinstance(op, ast.Pow):
                return le ** r
            if isinstance(op, ast.Div):
                return le / r
            raise _EvalError("binop")
        if isinstance(node, ast.Subscript):
            base = self.eval(node.value)
            sl = node.slice
            idx_nodes = list(sl.elts) if isinstance(sl, ast.Tuple) else [sl]
            if isinstance(base, _Table):
                idx_vals = [self.eval(n) for n in idx_nodes]
                return base.read(idx_nodes, idx_vals)
            idx = self.eval(sl)
            if isinstance(base, tuple) and isinstance(idx, int):
                return base[idx]
            raise _EvalError("subscript")
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, ast.IfExp):
            return self.eval(node.body) if self.eval(node.test) \
                else self.eval(node.orelse)
        if isinstance(node, ast.Compare) and len(node.ops) == 1:
            le, r = self.eval(node.left), self.eval(node.comparators[0])
            op = node.ops[0]
            if isinstance(op, ast.Lt):
                return le < r
            if isinstance(op, ast.LtE):
                return le <= r
            if isinstance(op, ast.Gt):
                return le > r
            if isinstance(op, ast.GtE):
                return le >= r
            if isinstance(op, ast.Eq):
                return le == r
            if isinstance(op, ast.NotEq):
                return le != r
        raise _EvalError(f"unsupported node {type(node).__name__}")

    def _call(self, node: ast.Call):
        name = call_name(node)
        leaf = name.split(".")[-1]
        args = [self.eval(a) for a in node.args]
        if leaf in ("minimum", "min"):
            return min(args)
        if leaf in ("maximum", "max"):
            return max(args)
        if leaf == "clip" and len(args) == 3:
            return min(max(args[0], args[1]), args[2])
        if leaf == "abs":
            return abs(args[0])
        if leaf == "cdiv" and len(args) == 2:
            return -(-args[0] // args[1])
        if leaf == "int32":
            return args[0]
        if leaf == "ceil":
            return math.ceil(args[0])
        # project helper (clamp functions): inline-evaluate its body
        fn = None
        if isinstance(node.func, ast.Name):
            fn = self.project.resolve_symbol(self.module, node.func.id)
        if fn is not None and isinstance(fn.node, ast.FunctionDef):
            return self._inline(fn, args)
        raise _EvalError(f"uneval call {name}")

    def _inline(self, fn: FunctionInfo, args: List[object]):
        local = dict(self.env)
        for p, v in zip(fn.positional_params, args):
            local[p] = v
        sub = _Evaluator(self.project, fn.module, local)
        for stmt in fn.node.body:
            if isinstance(stmt, ast.Assign) \
                    and isinstance(stmt.targets[0], ast.Name):
                local[stmt.targets[0].id] = sub.eval(stmt.value)
            elif isinstance(stmt, ast.Return) and stmt.value is not None:
                return sub.eval(stmt.value)
        raise _EvalError(f"helper {fn.qualname} has no return")


def _const_env(project: Project, fn: FunctionInfo) -> Dict[str, object]:
    """Seed shapes + module constants + param defaults + a forward pass
    over the wrapper's simple assignments (failures keep the seeds)."""
    env: Dict[str, object] = dict(_SEED_ENV)
    for stmt in fn.module.tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            val = literal_or_none(stmt.value)
            if isinstance(val, (int, float)):
                env[stmt.targets[0].id] = val
    a = fn.node.args
    pos = a.posonlyargs + a.args
    for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        val = literal_or_none(d)
        if isinstance(val, (int, float)):
            env[p.arg] = val
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is not None:
            val = literal_or_none(d)
            if isinstance(val, (int, float)):
                env[p.arg] = val
    ev = _Evaluator(project, fn.module, env)
    for stmt in ast.walk(fn.node):
        if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
            continue
        tgt = stmt.targets[0]
        try:
            if isinstance(tgt, ast.Name):
                if tgt.id != "_":
                    env[tgt.id] = ev.eval(stmt.value)
            elif isinstance(tgt, ast.Tuple) \
                    and all(isinstance(e, ast.Name) for e in tgt.elts):
                vals = ev.eval(stmt.value)
                if isinstance(vals, tuple) \
                        and len(vals) == len(tgt.elts):
                    for e, v in zip(tgt.elts, vals):
                        if e.id != "_":
                            env[e.id] = v
        except _EvalError:
            pass                         # shapes etc.: seeds stand in
    return env


# --------------------------------------------------------------------------
# pallas_call site parsing
# --------------------------------------------------------------------------

class _Site:
    def __init__(self, call: ast.Call):
        self.call = call
        # None: not a literal, so the arity checks that need it are off
        self.n_prefetch: Optional[int] = 0
        self.grid_expr: Optional[ast.expr] = None
        self.in_specs_expr: Optional[ast.expr] = None
        self.out_specs_expr: Optional[ast.expr] = None
        self.scratch_expr: Optional[ast.expr] = None

    @property
    def kernel_expr(self) -> Optional[ast.expr]:
        return self.call.args[0] if self.call.args else None


def _parse_site(call: ast.Call) -> _Site:
    site = _Site(call)
    kw = {k.arg: k.value for k in call.keywords}
    spec = kw.get("grid_spec")
    if isinstance(spec, ast.Call) \
            and call_name(spec).split(".")[-1] in (
                "PrefetchScalarGridSpec", "GridSpec"):
        skw = {k.arg: k.value for k in spec.keywords}
        n = literal_or_none(skw.get("num_scalar_prefetch")) \
            if skw.get("num_scalar_prefetch") is not None else 0
        site.n_prefetch = n if isinstance(n, int) else None
        site.grid_expr = skw.get("grid")
        site.in_specs_expr = skw.get("in_specs")
        site.out_specs_expr = skw.get("out_specs")
        site.scratch_expr = skw.get("scratch_shapes")
    else:
        site.grid_expr = kw.get("grid")
        site.in_specs_expr = kw.get("in_specs")
        site.out_specs_expr = kw.get("out_specs")
        site.scratch_expr = kw.get("scratch_shapes")
    return site


def _spec_count(expr: Optional[ast.expr]) -> Optional[int]:
    if expr is None:
        return 0
    if isinstance(expr, (ast.List, ast.Tuple)):
        if any(isinstance(e, ast.Starred) for e in expr.elts):
            return None
        return len(expr.elts)
    if isinstance(expr, ast.Call):
        return 1                         # a single BlockSpec / shape
    return None                          # built dynamically


def _index_maps(fn: FunctionInfo):
    """Every ``pl.BlockSpec(shape, index_map)`` in the wrapper: yields
    (display name, lineno, params, variadic, body-or-None), ``variadic``
    when the map takes ``*args`` after its named params."""
    seen = set()
    for node in ast.walk(fn.node):
        if not (isinstance(node, ast.Call)
                and call_name(node).split(".")[-1] == "BlockSpec"
                and len(node.args) >= 2):
            continue
        m = node.args[1]
        if isinstance(m, ast.Lambda):
            params = [p.arg for p in m.args.posonlyargs + m.args.args]
            yield ("<lambda>", m.lineno, params, m.args.vararg is not None,
                   m.body)
        elif isinstance(m, ast.Name):
            target = fn.module.functions.get(f"{fn.qualname}.{m.id}") \
                or fn.module.functions.get(m.id)
            if target is None or target.ref in seen:
                continue
            seen.add(target.ref)
            body = None
            for stmt in target.node.body:
                if isinstance(stmt, ast.Return):
                    body = stmt.value
            yield (m.id, target.node.lineno, target.positional_params,
                   target.node.args.vararg is not None, body)


def _resolve_kernel(fn: FunctionInfo, expr: Optional[ast.expr],
                    project: Project):
    """(kernel FunctionInfo, partial-bound kw names) for the body arg."""
    if expr is None:
        return None, set()
    if isinstance(expr, ast.Name):
        # local ``kernel = functools.partial(...)`` assignment
        for stmt in ast.walk(fn.node):
            if isinstance(stmt, ast.Assign) \
                    and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and stmt.targets[0].id == expr.id:
                return _resolve_kernel(fn, stmt.value, project)
        target = project.resolve_symbol(fn.module, expr.id)
        return target, set()
    if isinstance(expr, ast.Call) \
            and call_name(expr).split(".")[-1] == "partial" and expr.args:
        inner, bound = _resolve_kernel(fn, expr.args[0], project)
        return inner, bound | {k.arg for k in expr.keywords
                               if k.arg is not None}
    return None, set()


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

class KernelChecker:
    def __init__(self, project: Project):
        self.project = project

    def check(self) -> List[Finding]:
        findings: List[Finding] = []
        for fn in self.project.all_functions():
            calls = [n for n in ast.walk(fn.node)
                     if isinstance(n, ast.Call)
                     and call_name(n).split(".")[-1] == "pallas_call"]
            for call in calls:
                self._check_site(fn, _parse_site(call), findings)
        return findings

    # ------------------------------------------------------------------
    def _check_site(self, fn: FunctionInfo, site: _Site,
                    findings: List[Finding]) -> None:
        env = _const_env(self.project, fn)
        ev = _Evaluator(self.project, fn.module, env)
        grid: Optional[Tuple[int, ...]] = None
        if site.grid_expr is not None:
            try:
                g = ev.eval(site.grid_expr)
                if isinstance(g, tuple) \
                        and all(isinstance(x, int) for x in g):
                    grid = g
                elif isinstance(g, int):
                    grid = (g,)
            except _EvalError:
                pass

        n_in = _spec_count(site.in_specs_expr)
        n_out = _spec_count(site.out_specs_expr)
        if site.out_specs_expr is None:
            # no out_specs: outputs are implied by out_shape
            kw = {k.arg: k.value for k in site.call.keywords}
            n_out = _spec_count(kw.get("out_shape")) \
                if "out_shape" in kw else None
        n_scr = _spec_count(site.scratch_expr)

        # (a) index-map arity + (d) page-walk boundedness
        if grid is not None:
            n_pre = site.n_prefetch
            want = len(grid) + (n_pre or 0)
            for name, lineno, params, variadic, body in _index_maps(fn):
                if variadic:
                    ok = n_pre is None or len(params) <= want
                elif n_pre is None:
                    ok = len(params) >= len(grid)
                else:
                    ok = len(params) == want
                if not ok:
                    findings.append(Finding(
                        RULE, fn.module.rel, fn.qualname,
                        f"kernel.index-map-arity.{name}",
                        f"index_map `{name}` takes {len(params)} args but "
                        f"the grid has {len(grid)} dims + "
                        f"{n_pre} scalar-prefetch refs "
                        f"(= {want})", lineno))
                    continue
                if body is not None:
                    # the named params past the grid dims are prefetch refs
                    self._walk_check(fn, env, grid, len(params) - len(grid),
                                     name, lineno, params, body, findings)

        # (b) kernel body arity
        kernel, bound = _resolve_kernel(fn, site.kernel_expr, self.project)
        if kernel is not None \
                and None not in (site.n_prefetch, n_in, n_out, n_scr) \
                and isinstance(kernel.node, ast.FunctionDef) \
                and kernel.node.args.vararg is None:
            free = [p for p in kernel.positional_params if p not in bound]
            want = site.n_prefetch + n_in + n_out + n_scr
            if len(free) != want:
                findings.append(Finding(
                    RULE, fn.module.rel, fn.qualname,
                    f"kernel.body-arity.{kernel.qualname}",
                    f"kernel body `{kernel.qualname}` has {len(free)} "
                    f"unbound positional refs but the specs imply "
                    f"{site.n_prefetch} prefetch + {n_in} in + {n_out} "
                    f"out + {n_scr} scratch = {want}",
                    site.call.lineno))

        # (c) operand count at the immediate call
        outer = self._outer_call(fn, site.call)
        if outer is not None and None not in (site.n_prefetch, n_in) \
                and not any(isinstance(a, ast.Starred) for a in outer.args):
            want = site.n_prefetch + n_in
            if len(outer.args) != want:
                findings.append(Finding(
                    RULE, fn.module.rel, fn.qualname,
                    "kernel.operand-count",
                    f"pallas_call is invoked with {len(outer.args)} "
                    f"operands but the specs imply {site.n_prefetch} "
                    f"prefetch + {n_in} inputs = {want}", outer.lineno))

        # (e) output-store dtype agreement
        if kernel is not None and isinstance(kernel.node, ast.FunctionDef):
            self._dtype_check(kernel, findings)

    # ------------------------------------------------------------------
    def _outer_call(self, fn: FunctionInfo,
                    inner: ast.Call) -> Optional[ast.Call]:
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Call) and node.func is inner:
                return node
        return None

    def _walk_check(self, fn, env, grid, n_prefetch, name, lineno, params,
                    body, findings) -> None:
        if not grid or math.prod(grid) > _GRID_CAP:
            return
        mb = env.get("MB", _SEED_ENV["MB"])
        bs = env.get("BS", _SEED_ENV["BS"])
        cap = mb * bs
        chunk = env.get("W", _SEED_ENV["W"])
        for live in _LIVE_SET:
            if live > cap:
                continue
            tables = [_Table(live, live + chunk) for _ in range(n_prefetch)]
            for point in itertools.product(*(range(d) for d in grid)):
                local = dict(env)
                for p, v in zip(params, list(point) + tables):
                    local[p] = v
                try:
                    _Evaluator(self.project, fn.module, local).eval(body)
                except _EvalError:
                    return               # can't evaluate: stay quiet
            cols = [c for t in tables for c in t.cols]
            if not cols:
                return                   # no table access in this map
            last_live = max(-(-live // bs) - 1, 0)
            bad = [c for c in cols if c < 0 or c >= mb or c > last_live]
            if bad:
                findings.append(Finding(
                    RULE, fn.module.rel, fn.qualname,
                    f"kernel.page-walk-unbounded.{name}",
                    f"index_map `{name}` reads block-table column "
                    f"{max(bad)} with only {live} live tokens "
                    f"(last live page {last_live}, table width {mb}) — "
                    "clamp the walk to the live prefix "
                    "(see _clamp_live / _chunk_clamp)", lineno))
                return

    def _dtype_check(self, kernel: FunctionInfo,
                     findings: List[Finding]) -> None:
        for node in ast.walk(kernel.node):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Subscript)
                    and isinstance(node.targets[0].value, ast.Name)):
                continue
            oname = node.targets[0].value.id
            if not _OUT_REF_RE.match(oname):
                continue
            src = ast.unparse(node.value)
            if f".astype({oname}.dtype)" in src:
                continue
            # a pure ref-to-ref copy keeps the dtype by construction
            if isinstance(node.value, ast.Subscript) \
                    and isinstance(node.value.value, ast.Name) \
                    and node.value.value.id.endswith("_ref"):
                continue
            findings.append(Finding(
                RULE, kernel.module.rel, kernel.qualname,
                "kernel.out-dtype",
                f"store to `{oname}` does not `.astype({oname}.dtype)` — "
                "an f32 accumulator write silently changes the kernel's "
                "output dtype under interpret and fails on TPU",
                node.lineno))


def check_kernel_contracts(project: Project) -> List[Finding]:
    return finalize_occurrences(KernelChecker(project).check())
