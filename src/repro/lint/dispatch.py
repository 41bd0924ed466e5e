"""Pass 2 — dispatch hygiene.

The exact retrace/host-sync bug classes PRs 2, 3 and 6 fixed by hand:

- ``dispatch-jit-scope``: ``jax.jit`` applied inside a function body
  builds a fresh traced callable per call — the 70x dispatch regression.
  Jit wrapping belongs at module scope (or under ``lru_cache``).
- ``dispatch-jit-loop``: a jit-wrapped closure/lambda constructed inside
  a loop retraces on every iteration.
- ``dispatch-const-asarray``: ``jnp.asarray(MODULE_CONST)`` in a
  function body re-uploads the constant per call; memoize it
  (``lru_cache`` device-constant helper) or hoist to module scope.
  Exempt when the enclosing function is itself memoized or traced, or
  when every storage call site of it sits inside a traced function
  (the upload folds into the trace).
- ``dispatch-host-sync``: in data-plane hot paths (``*_begin`` /
  ``*_issue`` functions), ``.block_until_ready()`` / ``.item()`` — and
  ``np.asarray``/``float()`` applied to values produced by a device
  dispatch — force a device sync in the phase that exists to overlap
  with host work.
"""

from __future__ import annotations

import ast
import re

from repro.lint.core import (Finding, Module, Program, dotted,
                             is_jit_decorated, jit_call_target)

SCOPE_RULE = "dispatch-jit-scope"
LOOP_RULE = "dispatch-jit-loop"
CONST_RULE = "dispatch-const-asarray"
SYNC_RULE = "dispatch-host-sync"

JNP_ASARRAY = {"jnp.asarray", "jnp.array", "jax.numpy.asarray",
               "jax.numpy.array"}
NP_HOST = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
           "float", "int"}
SYNC_ATTRS = {"block_until_ready", "item"}
HOT_SUFFIXES = ("_begin", "_issue")

# functions whose return values live on device: materializing them on the
# host inside a begin/issue phase is a forced sync
DEVICE_PRODUCERS = {
    "rs_apply", "gear_hash", "gear_fire", "gear_fire_issue",
    "sha1_digest_words", "sha1_digest_flat", "gf_matmul",
    "fused_hash_encode_blobs", "flash_attention",
}

CONST_RE = re.compile(r"^[A-Z][A-Z0-9_]*$")


def _const_base_name(expr: ast.AST) -> str | None:
    """Final ALL_CAPS segment of e.g. ``hashing.SHA1_H0.astype(...)``."""
    while isinstance(expr, ast.Call) and isinstance(expr.func, ast.Attribute):
        expr = expr.func.value  # unwrap method chains (.astype/.reshape/...)
    if isinstance(expr, ast.Attribute):
        name = expr.attr
    elif isinstance(expr, ast.Name):
        name = expr.id
    else:
        return None
    return name if CONST_RE.match(name) and len(name) > 1 else None


def _producer_map(fn: ast.AST) -> dict[str, str]:
    """var -> last segment of the callee it was assigned from."""
    out: dict[str, str] = {}
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)):
            callee = dotted(node.value.func)
            if callee:
                out[node.targets[0].id] = callee.split(".")[-1]
    return out


def _called_only_from_traced(program: Program, mod: Module,
                             fname: str) -> bool:
    """True if every storage call site of ``fname`` is inside a traced
    (jitted) function — then a per-call constant upload folds into the
    trace and happens once per compile, not once per call."""
    sites = 0
    for m in program.storage_modules:
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if not name or name.split(".")[-1] != fname:
                continue
            if "." in name and m is not mod:
                stem = m.imports.get(name.split(".")[0])
                if stem != mod.stem:
                    continue
            elif "." not in name and m is not mod:
                continue
            sites += 1
            owner = program.enclosing_func(node)
            if owner is None or not owner.jitted:
                return False
    return sites > 0


class _Visitor(ast.NodeVisitor):
    def __init__(self, program: Program, mod: Module,
                 findings: list[Finding]):
        self.program = program
        self.mod = mod
        self.findings = findings
        self.func_stack: list[ast.AST] = []
        self.producer_stack: list[dict[str, str]] = []
        self.loop_depth = 0

    def _flag(self, node: ast.AST, rule: str, msg: str) -> None:
        self.findings.append(Finding(path=str(self.mod.path),
                                     line=node.lineno, rule=rule,
                                     message=msg))

    # -- scope tracking -------------------------------------------------
    def _visit_func(self, node: ast.AST) -> None:
        if (self.func_stack and is_jit_decorated(node)
                and not self._in_memo_factory()):
            rule = LOOP_RULE if self.loop_depth else SCOPE_RULE
            self._flag(node, rule,
                       f"`@jax.jit` on `{node.name}` at non-module scope "
                       "builds a fresh traced callable per call")
        self.func_stack.append(node)
        self.producer_stack.append(_producer_map(node))
        outer_depth, self.loop_depth = self.loop_depth, 0
        # decorators evaluate in the enclosing scope: the def-level rule
        # above covers them, so don't re-visit them as body expressions
        for child in ast.iter_child_nodes(node):
            if any(child is dec for dec in node.decorator_list):
                continue
            self.visit(child)
        self.loop_depth = outer_depth
        self.producer_stack.pop()
        self.func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def _visit_loop(self, node: ast.AST) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    # -- the rules ------------------------------------------------------
    def _in_hot_func(self) -> bool:
        return any(getattr(f, "name", "").endswith(HOT_SUFFIXES)
                   for f in self.func_stack)

    def _in_memo_factory(self) -> bool:
        """A jit constructed under an lru_cache'd factory is built once."""
        from repro.lint.core import is_memo_decorated
        return any(is_memo_decorated(f) for f in self.func_stack)

    def visit_Call(self, node: ast.Call) -> None:
        name = dotted(node.func)
        if (jit_call_target(node) is not None and self.func_stack
                and not self._in_memo_factory()):
            if self.loop_depth:
                self._flag(node, LOOP_RULE,
                           "`jax.jit(...)` constructed inside a loop "
                           "retraces every iteration; hoist to module "
                           "scope")
            else:
                self._flag(node, SCOPE_RULE,
                           "`jax.jit(...)` at non-module scope builds a "
                           "fresh traced callable per call; hoist or "
                           "memoize")
        elif name in JNP_ASARRAY and node.args and self.func_stack:
            const = _const_base_name(node.args[0])
            if const is not None and not self._const_exempt():
                self._flag(node, CONST_RULE,
                           f"`{name}({const}...)` re-uploads a module "
                           "constant per call; memoize the device copy "
                           "(lru_cache) or hoist to module scope")
        if self._in_hot_func():
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in SYNC_ATTRS):
                self._flag(node, SYNC_RULE,
                           f"`.{node.func.attr}()` forces a device sync "
                           "inside a begin/issue hot path")
            elif (name in NP_HOST and node.args
                  and isinstance(node.args[0], ast.Name)):
                producers = self.producer_stack[-1] if self.producer_stack else {}
                src = producers.get(node.args[0].id)
                if src is not None and (
                        src in DEVICE_PRODUCERS
                        or self.program.is_jitted_callable(self.mod, src)):
                    self._flag(node, SYNC_RULE,
                               f"`{name}({node.args[0].id})` materializes "
                               f"the device result of `{src}` inside a "
                               "begin/issue hot path")
        self.generic_visit(node)

    def _const_exempt(self) -> bool:
        fn = self.func_stack[-1]
        owner = self.program.enclosing_func(fn)
        for info in ([owner] if owner else []):
            if info.jitted or info.memoized:
                return True
        # innermost def may be a nested helper with its own decorators
        from repro.lint.core import is_memo_decorated
        if is_memo_decorated(fn) or is_jit_decorated(fn):
            return True
        if owner is not None and _called_only_from_traced(
                self.program, self.mod, owner.name):
            return True
        return False


def run(program: Program) -> list[Finding]:
    findings: list[Finding] = []
    for mod in program.storage_modules:
        _Visitor(program, mod, findings).visit(mod.tree)
    return findings
