"""DP204: donated buffers read after donation.

Every train-step factory in `tpu_dp.train.step` compiles with
``donate_argnums=(0,)`` — the caller's `TrainState` buffers are handed to
XLA for reuse, and the Python object left behind is dead: reading it after
the call returns garbage on real backends (or raises a deleted-buffer
error). The correct idiom rebinds at the call site::

    state, metrics = train_step(state, batch)   # donated AND rebound: ok
    new_state, _ = train_step(state, batch)
    state.params                                 # DP204: read after donation

The check is a line-ordered dataflow approximation per function scope:
variables (or ``self.x`` attributes) holding the result of a known
donating factory are tracked; a call through one donates its first
argument; a later load of that name without an intervening rebinding is
flagged. Control flow inside the scope is ignored (documented
approximation — rebinding in a loop header counts, branches are merged).
"""

from __future__ import annotations

import ast
from typing import Iterable

from tpu_dp.analysis import pragmas
from tpu_dp.analysis.astlint import _dotted, iter_py_files, scope_index, \
    scope_at
from tpu_dp.analysis.report import Finding

# The factory of every train program, each jitted with
# donate_argnums=(0,): calling the result consumes its first argument.
DONATING_FACTORIES = {"make_train_step"}

# Wrappers that preserve the donating call signature: a name bound to
# `RecompileGuard(make_train_step(...))` or the trainer's
# `self._guarded("train_step", make_train_step(...))` still donates its
# first argument when called.
_TRANSPARENT_WRAPPERS = {"RecompileGuard", "_guarded"}


def _target_names(target: ast.AST) -> list[str]:
    """Dotted names assigned by a target (unpacks tuples/lists)."""
    if isinstance(target, (ast.Tuple, ast.List)):
        out: list[str] = []
        for el in target.elts:
            out.extend(_target_names(el))
        return out
    dotted = _dotted(target)
    return [dotted] if dotted else []


def _collect_step_fn_names(tree: ast.Module) -> set[str]:
    """Names (incl. `self.attr`) bound to a donating factory's result."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not isinstance(value, ast.Call):
            continue
        dotted = _dotted(value.func)
        if dotted and dotted.rsplit(".", 1)[-1] in _TRANSPARENT_WRAPPERS:
            inner = next(
                (a for a in value.args if isinstance(a, ast.Call)), None
            )
            if inner is not None:
                value = inner
                dotted = _dotted(value.func)
        if dotted and dotted.rsplit(".", 1)[-1] in DONATING_FACTORIES:
            for target in node.targets:
                names.update(_target_names(target))
    return names


def _walk_scope(fn: ast.AST):
    """Every node lexically in a function, not descending into nested
    function/class scopes (their dataflow is their own)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            stack.append(child)


def _check_scope(
    fn: ast.AST,
    step_fns: set[str],
    path: str,
    allowed: dict[int, set[str]],
    scopes: list[tuple[int, int, str]] | None = None,
) -> list[Finding]:
    # (donated_name, donation_line, donation_end_line) events and
    # (name, line) stores/loads, all in source-line order — the
    # control-flow-free approximation. The end line matters for calls that
    # span lines: the donated argument's own Load inside the call is not a
    # read-after-donation.
    donations: list[tuple[str, int, int]] = []
    stores: list[tuple[str, int]] = []
    loads: list[tuple[str, int, int]] = []  # name, line, col

    for node in _walk_scope(fn):
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            if dotted and (dotted in step_fns or
                           dotted.rsplit(".", 1)[-1] in step_fns):
                if node.args:
                    donated = _dotted(node.args[0])
                    if donated:
                        donations.append((donated, node.lineno,
                                          node.end_lineno or node.lineno))
        if isinstance(node, (ast.Name, ast.Attribute)):
            dotted = _dotted(node)
            if dotted is None:
                continue
            ctx = getattr(node, "ctx", None)
            if isinstance(ctx, ast.Store):
                stores.append((dotted, node.lineno))
            elif isinstance(ctx, ast.Load):
                loads.append((dotted, node.lineno,
                              getattr(node, "col_offset", 0)))

    findings: list[Finding] = []
    flagged: set[tuple[str, int]] = set()
    for name, dline, dend in donations:
        # A store on the donation line (the `state, m = step(state, ...)`
        # rebinding) or any later line revives the name.
        revive = [sl for n, sl in stores if n == name and sl >= dline]
        revive_line = min(revive) if revive else None
        for lname, lline, _ in loads:
            if lname != name and not lname.startswith(name + "."):
                continue
            if lline <= dend:
                continue
            if revive_line is not None and revive_line <= lline:
                continue
            key = (name, lline)
            if key in flagged:
                continue
            flagged.add(key)
            if not pragmas.is_allowed(allowed, "DP204", (lline, dline)):
                findings.append(Finding(
                    "DP204", path, lline,
                    f"`{name}` was donated to a compiled step at line "
                    f"{dline} (donate_argnums) and read afterwards — its "
                    f"buffers now belong to XLA; rebind the step's result "
                    f"to `{name}` instead",
                    symbol=scope_at(scopes, lline) if scopes else "",
                ))
    return findings


def check_source(path: str, source: str) -> list[Finding]:
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return []  # astlint reports the parse failure
    step_fns = _collect_step_fn_names(tree)
    if not step_fns:
        return []
    allowed = pragmas.collect(source)
    index = scope_index(tree)
    findings: list[Finding] = []
    scopes: list[ast.AST] = [
        node for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for scope in scopes:
        findings.extend(_check_scope(scope, step_fns, path, allowed, index))
    return findings


def check_paths(paths: Iterable[str]) -> list[Finding]:
    findings: list[Finding] = []
    for path in iter_py_files(paths):
        with open(path, encoding="utf-8") as f:
            findings.extend(check_source(path, f.read()))
    return findings
