"""Static Pallas grid-semantics race checker (DESIGN.md §14).

Mosaic executes a ``pallas_call`` grid sequentially unless
``compiler_params.dimension_semantics`` marks axes ``"parallel"`` — and
our kernels depend on that default: ``mxint_matmul`` accumulates into a
f32 VMEM scratch across the K axis, ``mxint_ln_matmul`` keeps its
normalised tile resident across the N axis, the flash kernels carry
(m, l, acc) online-softmax state across the key axis.  Re-ordering (or
multi-core-partitioning) those axes is a data race; re-ordering the
independent tile axes is free parallelism.  This pass makes the contract
explicit and machine-checked, per captured call:

1. **Revisit inference** — each ref's ``index_map`` is probed per grid
   axis (holding the other axes at the grid corners): an axis the map
   does not depend on revisits the same block on every step of that
   axis.  An OUTPUT revisited along an axis is written on multiple steps
   — that axis needs ``"arbitrary"`` ordering.
2. **Accumulator-gate inference** — the kernel body (and one level of
   helpers it forwards ``program_id`` values to) is AST-scanned for
   ``pl.when(program_id(a) == ...)`` gates, resolving comparators
   through the ``functools.partial`` keywords the wrappers bind
   (``n_k - 1`` really is the last step of THIS grid).  A gated axis
   carries scratch state sequentially and needs ``"arbitrary"``.
3. **Declaration check** — every call must declare
   ``dimension_semantics``; a required-sequential axis declared
   ``"parallel"`` is a race (ERROR), an independent axis declared
   ``"arbitrary"`` is contradictory serialisation (ERROR, only when the
   kernel source was inspectable), missing/short declarations are
   ERRORs.
4. **Ordering hazards** — accumulator init gates must fire on step 0 and
   output flush gates on the LAST step of their axis; a reversed or
   interior (or dead, out-of-range) gate flushes garbage (ERROR).
5. **Unaliased in-place outputs** — a kernel that READS an output ref
   sees uninitialised VMEM on a block's first visit unless an input is
   aliased over it via ``input_output_aliases`` (ERROR; accumulate in
   scratch instead).

The rule walks the same abstract-eval sweep as ``kernel_contracts``
(shared memo), so every kernel in ``repro/kernels/`` is covered at the
stand-alone + DeiT shapes.
"""
from __future__ import annotations

import ast
import dataclasses
import functools
import inspect
import textwrap
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.kernel_contracts import (BlockUse, PallasCapture,
                                             sweep_captures)
from repro.analysis.registry import ERROR, Violation, register_rule

VALID_SEMANTICS = ("parallel", "arbitrary")
_MAX_HELPER_DEPTH = 2


# ---------------------------------------------------------------------------
# 1. index-map axis dependence
# ---------------------------------------------------------------------------
def map_axis_dependence(use: BlockUse, grid: Tuple[int, ...]) -> Set[int]:
    """Grid axes ``use.index_map`` depends on, probed along each axis with
    the other axes pinned at the grid's corners (affine maps — the only
    kind BlockSpecs use — cannot hide a dependence from both corners)."""
    im = use.index_map
    if im is None:
        return set()
    deps: Set[int] = set()
    corners = [tuple(0 for _ in grid), tuple(g - 1 for g in grid)]
    for a, ga in enumerate(grid):
        if ga <= 1:
            continue
        for base in corners:
            seen = set()
            for v in range(ga):
                idx = list(base)
                idx[a] = v
                bid = im(*idx)
                bid = tuple(bid) if isinstance(bid, (list, tuple)) else (bid,)
                seen.add(tuple(int(b) for b in bid))
            if len(seen) > 1:
                deps.add(a)
                break
    return deps


def output_revisit_axes(cap: PallasCapture) -> Set[int]:
    """Axes along which some output block is written more than once."""
    out: Set[int] = set()
    for use in cap.outputs:
        deps = map_axis_dependence(use, cap.grid)
        for a, ga in enumerate(cap.grid):
            if ga > 1 and a not in deps:
                out.add(a)
    return out


# ---------------------------------------------------------------------------
# 2. AST accumulator-gate inference
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Gate:
    """One ``pl.when(...)`` whose predicate involves a ``program_id``."""

    axis: int
    is_eq: bool                    # equality predicate (init/flush shape)
    value: Optional[int]           # resolved comparator, None if opaque
    writes: Tuple[str, ...]        # ref roles stored in the gated body


def _unwrap_partial(kernel):
    env: Dict[str, object] = {}
    n_pos = 0
    fn = kernel
    while isinstance(fn, functools.partial):
        env.update(fn.keywords or {})
        n_pos += len(fn.args or ())
        fn = fn.func
    return fn, env, n_pos


def _fn_node(fn) -> Optional[ast.FunctionDef]:
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError, ValueError):
        return None
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _pid_axis(node: ast.AST, axis_alias: Dict[str, int]) -> Optional[int]:
    """Axis index if ``node`` is ``pl.program_id(<const>)`` or an alias."""
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if (name is not None and name.split(".")[-1] == "program_id"
                and node.args and isinstance(node.args[0], ast.Constant)):
            return int(node.args[0].value)
    if isinstance(node, ast.Name) and node.id in axis_alias:
        return axis_alias[node.id]
    return None


def _eval_expr(node: ast.AST, env: Dict[str, object]) -> Optional[int]:
    """Resolve a comparator expression against the partial-keyword env."""
    try:
        code = compile(ast.fix_missing_locations(
            ast.Expression(body=node)), "<gate>", "eval")
        val = eval(code, {"__builtins__": {}}, dict(env))  # noqa: S307
    except Exception:
        return None
    return int(val) if isinstance(val, (int, float)) and not isinstance(
        val, bool) else None


def _written_roles(body: Sequence[ast.stmt],
                   roles: Dict[str, str]) -> Tuple[str, ...]:
    found: Set[str] = set()
    for stmt in body:
        for node in ast.walk(stmt):
            tgt = None
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Name):
                        tgt = t.value.id
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target, ast.Subscript) and \
                        isinstance(node.target.value, ast.Name):
                    tgt = node.target.value.id
            if tgt is not None and tgt in roles:
                found.add(roles[tgt])
    return tuple(sorted(found))


@dataclasses.dataclass
class _BodyFacts:
    gates: List[Gate] = dataclasses.field(default_factory=list)
    output_reads: Set[str] = dataclasses.field(default_factory=set)
    src_ok: bool = True


def _scan_function(fn, env: Dict[str, object], roles: Dict[str, str],
                   axis_alias: Dict[str, int], facts: _BodyFacts,
                   depth: int) -> None:
    node = _fn_node(fn)
    if node is None:
        facts.src_ok = False
        return
    axis_alias = dict(axis_alias)

    # program_id aliases assigned in this body (``kb = pl.program_id(2)``)
    for stmt in ast.walk(node):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            axis = _pid_axis(stmt.value, {})
            if axis is not None:
                axis_alias[stmt.targets[0].id] = axis

    for sub in ast.walk(node):
        # pl.when-decorated inner functions
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in sub.decorator_list:
                if not (isinstance(deco, ast.Call) and deco.args):
                    continue
                dname = _dotted(deco.func)
                if dname is None or dname.split(".")[-1] != "when":
                    continue
                pred = deco.args[0]
                if not isinstance(pred, ast.Compare) or len(pred.ops) != 1:
                    continue
                left, op, right = pred.left, pred.ops[0], pred.comparators[0]
                axis = _pid_axis(left, axis_alias)
                other = right
                if axis is None:
                    axis = _pid_axis(right, axis_alias)
                    other = left
                if axis is None:
                    continue
                facts.gates.append(Gate(
                    axis=axis, is_eq=isinstance(op, ast.Eq),
                    value=_eval_expr(other, env),
                    writes=_written_roles(sub.body, roles)))
        # in-place reads of output refs (Subscript load / AugAssign)
        if isinstance(sub, ast.Subscript) and isinstance(sub.value, ast.Name):
            name = sub.value.id
            if roles.get(name) == "output" and (
                    isinstance(sub.ctx, ast.Load)
                    or isinstance(sub.ctx, ast.AugStore)
                    if hasattr(ast, "AugStore") else False):
                facts.output_reads.add(name)
        if isinstance(sub, ast.AugAssign) and \
                isinstance(sub.target, ast.Subscript) and \
                isinstance(sub.target.value, ast.Name) and \
                roles.get(sub.target.value.id) == "output":
            facts.output_reads.add(sub.target.value.id)

    if depth >= _MAX_HELPER_DEPTH:
        return
    # one level of helper-call propagation: forward program_id aliases,
    # ref roles and resolvable values into same-module helpers
    globals_ = getattr(fn, "__globals__", {})
    for sub in ast.walk(node):
        if not (isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name)):
            continue
        target = globals_.get(sub.func.id)
        if not (inspect.isfunction(target) and target is not fn):
            continue
        tnode = _fn_node(target)
        if tnode is None:
            continue
        params = [a.arg for a in tnode.args.posonlyargs + tnode.args.args]
        kwparams = [a.arg for a in tnode.args.kwonlyargs]
        bound: List[Tuple[str, ast.AST]] = list(zip(params, sub.args))
        bound += [(kw.arg, kw.value) for kw in sub.keywords
                  if kw.arg is not None and kw.arg in params + kwparams]
        c_env: Dict[str, object] = {}
        c_roles: Dict[str, str] = {}
        c_alias: Dict[str, int] = {}
        for pname, arg in bound:
            if isinstance(arg, ast.Name):
                if arg.id in axis_alias:
                    c_alias[pname] = axis_alias[arg.id]
                elif arg.id in roles:
                    c_roles[pname] = roles[arg.id]
                elif arg.id in env:
                    c_env[pname] = env[arg.id]
            elif isinstance(arg, ast.Constant):
                c_env[pname] = arg.value
            else:
                axis = _pid_axis(arg, axis_alias)
                if axis is not None:
                    c_alias[pname] = axis
        _scan_function(target, c_env, c_roles, c_alias, facts, depth + 1)


def kernel_body_facts(cap: PallasCapture) -> _BodyFacts:
    """Gates, output reads and source availability for a capture's kernel."""
    facts = _BodyFacts()
    if cap.kernel_fn is None:
        facts.src_ok = False
        return facts
    fn, env, n_bound = _unwrap_partial(cap.kernel_fn)
    node = _fn_node(fn)
    if node is None:
        facts.src_ok = False
        return facts
    params = [a.arg for a in node.args.posonlyargs + node.args.args]
    params = params[n_bound:]
    n_in, n_out = len(cap.inputs), len(cap.outputs)
    n_scr = len(cap.scratch)
    if len(params) < n_in + n_out + n_scr and node.args.vararg is None:
        facts.src_ok = False
        return facts
    roles: Dict[str, str] = {}
    for i, p in enumerate(params[:n_in + n_out + n_scr]):
        roles[p] = ("input" if i < n_in
                    else "output" if i < n_in + n_out else "scratch")
    _scan_function(fn, env, roles, {}, facts, 0)
    return facts


# ---------------------------------------------------------------------------
# 3-5. the checks
# ---------------------------------------------------------------------------
def _where(cap: PallasCapture) -> str:
    return f"{cap.label}/{cap.kernel}"


def check_capture_semantics(cap: PallasCapture) -> List[Violation]:
    out: List[Violation] = []
    naxes = len(cap.grid)
    revisit = output_revisit_axes(cap)
    facts = kernel_body_facts(cap)
    gate_axes = {g.axis for g in facts.gates if 0 <= g.axis < naxes}
    required = revisit | gate_axes

    def _why(a: int) -> str:
        bits = []
        if a in revisit:
            bits.append("an output block is written on multiple steps")
        if a in gate_axes:
            bits.append("program_id-gated accumulator state crosses steps")
        return " and ".join(bits)

    ds = cap.dimension_semantics
    if ds is None:
        out.append(Violation(
            "grid-semantics", _where(cap),
            f"pallas_call declares no dimension_semantics for grid "
            f"{cap.grid}; required: "
            f"{tuple('arbitrary' if a in required else 'parallel' for a in range(naxes))} "
            f"(declare via compiler_params=pltpu.CompilerParams(...))"))
    elif len(ds) != naxes:
        out.append(Violation(
            "grid-semantics", _where(cap),
            f"dimension_semantics {ds} has {len(ds)} entries for a "
            f"{naxes}-axis grid {cap.grid}"))
    else:
        for a, sem in enumerate(ds):
            if sem not in VALID_SEMANTICS:
                out.append(Violation(
                    "grid-semantics", _where(cap),
                    f"axis {a}: unknown semantics {sem!r} "
                    f"(expected one of {VALID_SEMANTICS})"))
            elif a in required and sem != "arbitrary":
                out.append(Violation(
                    "grid-semantics", _where(cap),
                    f"axis {a} (size {cap.grid[a]}) declared "
                    f"{sem!r} but {_why(a)} — re-ordering this axis is a "
                    f"data race; declare it \"arbitrary\""))
            elif (a not in required and sem == "arbitrary"
                  and cap.grid[a] > 1 and facts.src_ok):
                out.append(Violation(
                    "grid-semantics", _where(cap),
                    f"axis {a} (size {cap.grid[a]}) declared \"arbitrary\" "
                    f"but no output revisit or accumulator gate depends on "
                    f"it — declare it \"parallel\" (free grid parallelism)"))

    # 4. init/flush ordering hazards
    for g in facts.gates:
        if not (g.is_eq and g.value is not None and 0 <= g.axis < naxes):
            continue
        last = cap.grid[g.axis] - 1
        if last <= 0:
            continue
        if "output" in g.writes:
            if g.value != last:
                out.append(Violation(
                    "grid-semantics", _where(cap),
                    f"axis {g.axis}: output flush gated on step {g.value} "
                    f"of {cap.grid[g.axis]} — results leave before the "
                    f"last accumulation step ({last})"))
        elif "scratch" in g.writes:
            if g.value != 0:
                out.append(Violation(
                    "grid-semantics", _where(cap),
                    f"axis {g.axis}: accumulator init gated on step "
                    f"{g.value} != 0 — earlier steps accumulate into "
                    f"uninitialised scratch"))
        elif g.value not in (0, last):
            out.append(Violation(
                "grid-semantics", _where(cap),
                f"axis {g.axis}: program_id equality gate on interior "
                f"step {g.value} (grid size {cap.grid[g.axis]}) — neither "
                f"the init (0) nor the flush ({last}) step"))

    # 5. unaliased in-place outputs
    if facts.output_reads:
        aliased_outputs = {dst for _, dst in cap.input_output_aliases}
        if len(aliased_outputs) < len(cap.outputs):
            out.append(Violation(
                "grid-semantics", _where(cap),
                f"kernel reads output ref(s) {sorted(facts.output_reads)} "
                f"in-place without input_output_aliases — the first visit "
                f"of a block reads uninitialised VMEM; alias an input over "
                f"the output or accumulate in scratch"))
    return out


def check_captures_semantics(
        caps: Sequence[PallasCapture]) -> List[Violation]:
    out: List[Violation] = []
    for cap in caps:
        out.extend(check_capture_semantics(cap))
    return out


@register_rule(
    "grid-semantics",
    "Pallas dimension_semantics race checker: accumulator axes declared "
    "\"arbitrary\", independent axes \"parallel\", init/flush ordering "
    "and output aliasing over the stand-alone + DeiT sweep")
def run(root: Path) -> List[Violation]:
    caps = sweep_captures()
    out = check_captures_semantics(caps)
    if not caps:
        out.append(Violation("grid-semantics", "sweep",
                             "sweep captured no pallas_calls — the "
                             "recorder or the kernels moved"))
    return out
