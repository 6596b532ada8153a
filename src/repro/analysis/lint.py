"""AST host-sync / tracer-leak lint over ``src/repro``.

The serving hot path lives or dies on never blocking the Python thread on
device values mid-tick (and never leaking tracers into Python control flow
inside jitted code).  This pass finds the syntactic shapes those bugs take:

  * ``host-item``     — ``x.item()``: always a device->host sync.
  * ``host-cast``     — ``int()/float()/bool()`` over an expression that
                        involves a device value (a ``jnp.*``/``jax.*`` call,
                        a call to a function imported from the model/kernel
                        layers, a jitted ``self._*`` engine function, or a
                        local previously bound to one).  Blocks until the
                        value is ready.
  * ``host-asarray``  — ``np.asarray()/np.array()/jax.device_get()`` over a
                        device value: the transfer that ends XLA's async
                        dispatch pipeline.
  * ``tracer-branch`` — Python ``if``/``while``/``assert`` on a device value
                        inside *traced* modules (models/core/kernels/quant):
                        under ``jit`` this is a ConcretizationTypeError at
                        best, a silently-specialized graph at worst.
  * ``debug-call``    — ``jax.debug.print/callback/breakpoint`` left in the
                        serving/training code (each is a host callback that
                        serializes the step).
  * ``block-sync``    — ``jax.block_until_ready`` / ``.block_until_ready()``
                        in hot modules; legitimate only as a deliberate
                        timing fence (pragma it with the justification).

Device-ness is inferred per function with a single in-order pass: calls
rooted at ``jnp.``/``jax.`` are device-producing, as are names imported from
modules matching ``device_import_re`` (the traced layers) and calls to
``self._*`` attributes in engine modules (the jitted fns); assignment
propagates it to the bound names.  Attribute reads of static metadata
(``.shape``/``.ndim``/``.dtype``/``.size``) are NOT device values — casting
a shape is free and idiomatic.

Severity comes from the module map: findings in hot modules (serving /
models / kernels / core / quant) are **errors**, in cold modules (launch
CLIs, training drivers, data, obs, ...) **warnings** — a host sync in a
results printer is fine, but the map keeps it visible so hot code cannot be
pasted there and drift back.

Suppression: ``# analysis: allow(<rule>[, <rule>...]) — <one-line why>`` on
the offending line, or alone on the line above it.  Suppressed findings are
still reported (inert) so pragma rot is visible; the justification text is
mandatory by convention, enforced by review rather than the parser.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.findings import Report

PRAGMA_RE = re.compile(r"#\s*analysis:\s*allow\(\s*([\w\-*,\s]+?)\s*\)")

RULES = ("host-item", "host-cast", "host-asarray", "tracer-branch",
         "debug-call", "block-sync")

# calls that copy device values to the host (blocking) and return host values
_TRANSFERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array", "jax.device_get"}

# attribute reads that are static metadata, not device values
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}

# jax/jnp-rooted calls that return HOST values (platform probes, static
# metadata, abstract evaluation) — not device arrays
_HOST_CALLS = {
    "jax.default_backend", "jax.devices", "jax.local_devices",
    "jax.device_count", "jax.local_device_count", "jax.process_index",
    "jax.process_count", "jax.eval_shape", "jax.make_jaxpr",
    "jax.tree.structure", "jax.tree_util.tree_structure",
    "jnp.ndim", "jnp.shape", "jnp.size", "jnp.dtype", "jnp.result_type",
    "jnp.issubdtype", "jnp.iinfo", "jnp.finfo",
}


@dataclass
class LintConfig:
    # module-path regexes (matched against the path relative to the scan
    # root, forward slashes) — hot findings are errors, cold are warnings
    hot_re: str = r"(serving|models|kernels|core|quant)/"
    # traced modules: code that runs under jit — tracer-branch applies here
    traced_re: str = r"(models|kernels|core|quant)/"
    # imports from these modules are device-producing callables
    device_import_re: str = (
        r"repro\.(models|kernels|core|quant|serving\.sampling)")
    # calls to self.<attr> matching this, in hot modules, produce device
    # values (the engines' jitted functions)
    jit_attr_re: str = r"^_(decode|prefill|reset|copy|make_caches)"
    # boolean predicates by naming convention (is_/has_/check_/spec_is_...)
    # return host bools even when imported from device modules
    host_fn_re: str = r"(^_?(is|has|check|can|supports)_)|(^spec_is_)|(_is_)"
    skip_re: str = r"analysis/"  # don't lint the linter's own fixtures

    def severity_for(self, relpath: str, rule: str) -> Optional[str]:
        hot = re.search(self.hot_re, relpath) is not None
        if rule == "tracer-branch":
            return "error" if re.search(self.traced_re, relpath) else None
        return "error" if hot else "warning"


def _pragmas(source: str) -> Dict[int, Set[str]]:
    """line number (1-based) -> set of allowed rules on that line.  A pragma
    on a comment-only line also covers the next line."""
    out: Dict[int, Set[str]] = {}
    lines = source.splitlines()
    for i, line in enumerate(lines, 1):
        m = PRAGMA_RE.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        out.setdefault(i, set()).update(rules)
        if line.strip().startswith("#"):
            out.setdefault(i + 1, set()).update(rules)
    return out


def _dotted(node: ast.AST) -> Optional[str]:
    """'jnp.einsum' / 'self._decode' / 'np.asarray' for an attr chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _FileLinter(ast.NodeVisitor):
    def __init__(self, relpath: str, source: str, cfg: LintConfig, report: Report):
        self.relpath = relpath
        self.cfg = cfg
        self.report = report
        self.pragmas = _pragmas(source)
        self.device_fns: Set[str] = set()  # module-level device-producing names
        self.scopes: List[Set[str]] = []  # per-function device-bound names

    # -- imports: which names are device-producing callables ---------------
    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module and re.search(self.cfg.device_import_re, node.module):
            for a in node.names:
                self.device_fns.add(a.asname or a.name)
        self.generic_visit(node)

    # -- device-ness --------------------------------------------------------
    def _call_is_device(self, call: ast.Call) -> bool:
        name = _dotted(call.func)
        if name is None:
            return False
        if re.search(self.cfg.host_fn_re, name.split(".")[-1]):
            return False  # boolean predicate by naming convention
        root = name.split(".")[0]
        if root in ("jnp", "jax"):
            if name in _HOST_CALLS:
                return False  # platform probe / static metadata, host value
            # jax.debug / block_until_ready have dedicated rules
            return not name.startswith(("jax.debug", "jax.block_until_ready"))
        if name in self.device_fns:
            return True
        if name.startswith("self."):
            attr = name.split(".", 1)[1]
            if re.match(self.cfg.jit_attr_re, attr):
                return True
        return False

    def _is_device(self, node: ast.AST) -> bool:
        """Does this expression involve a device value?  Static-metadata
        attribute reads (.shape etc.) cut the search."""
        for sub in self._walk_non_static(node):
            if isinstance(sub, ast.Call) and self._call_is_device(sub):
                return True
            if isinstance(sub, ast.Name) and self.scopes and sub.id in self.scopes[-1]:
                return True
        return False

    def _walk_non_static(self, node: ast.AST) -> Iterable[ast.AST]:
        yield node
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            # a comprehension's value is its ELEMENT: [leaf.shape[2] for leaf
            # in jax.tree.leaves(c)] is a host list of ints even though the
            # iterable is a device tree
            yield from self._walk_non_static(node.elt)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.Attribute) and child.attr in _STATIC_ATTRS:
                continue  # x.shape[...] is host-side metadata
            yield from self._walk_non_static(child)

    # -- scope handling ------------------------------------------------------
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.scopes.append(set())
        self.generic_visit(node)
        self.scopes.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _bind_targets(self, targets: Sequence[ast.AST]) -> None:
        if not self.scopes:
            return
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                self._bind_targets(t.elts)
            elif isinstance(t, ast.Name):
                self.scopes[-1].add(t.id)
            elif isinstance(t, ast.Starred):
                self._bind_targets([t.value])

    def visit_Assign(self, node: ast.Assign) -> None:
        # visit RHS first: `x = np.asarray(x_dev)` must flag the OLD x
        self.visit(node.value)
        for t in node.targets:  # subscript/attr targets can hold calls too
            if not isinstance(t, (ast.Name, ast.Tuple, ast.List, ast.Starred)):
                self.visit(t)
        root = _dotted(node.value.func) if isinstance(node.value, ast.Call) else None
        if root and (root.split(".")[0] == "np" or root in _TRANSFERS):
            pass  # np.* / device_get results are host values — the sync already happened
        elif self._is_device(node.value):
            self._bind_targets(node.targets)
        elif self.scopes:
            for t in node.targets:  # rebinding to a host value clears it
                if isinstance(t, ast.Name):
                    self.scopes[-1].discard(t.id)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)
        if self._is_device(node.value):
            self._bind_targets([node.target])

    # -- findings ------------------------------------------------------------
    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        sev = self.cfg.severity_for(self.relpath, rule)
        if sev is None:
            return
        line = getattr(node, "lineno", 0)
        allowed = self.pragmas.get(line, set())
        suppressed = rule in allowed or "*" in allowed
        self.report.add(rule, sev, f"{self.relpath}:{line}", message,
                        suppressed=suppressed)

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if name:
            if name.endswith(".item") and not node.args:
                self._emit("host-item", node,
                           "`.item()` forces a device->host sync")
            elif name in ("int", "float", "bool") and node.args \
                    and self._is_device(node.args[0]):
                self._emit("host-cast", node,
                           f"`{name}()` over a device value blocks on the result")
            elif name in _TRANSFERS and node.args and self._is_device(node.args[0]):
                self._emit("host-asarray", node,
                           f"`{name}` of a device value is a blocking transfer")
            elif name.startswith("jax.debug."):
                self._emit("debug-call", node,
                           f"`{name}` is a host callback; remove before serving")
            elif name == "jax.block_until_ready" or name.endswith(".block_until_ready"):
                self._emit("block-sync", node,
                           "explicit device fence in a hot module")
        self.generic_visit(node)

    def _check_branch(self, test: ast.AST, kind: str) -> None:
        # `x is None` / `x is not None` on a device name is a host-side
        # identity test, not a sync — common and fine
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return
        # `isinstance(x, QuantizedKV)` is pytree-node type dispatch — static
        # under tracing (tracers keep their pytree structure), not a sync
        if isinstance(test, ast.Call) and _dotted(test.func) == "isinstance":
            return
        if self._is_device(test):
            self._emit("tracer-branch", test,
                       f"Python `{kind}` on a device value — under jit this "
                       "is a tracer leak (concretization)")

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node.test, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node.test, "while")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        self._check_branch(node.test, "assert")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_branch(node.test, "if-expression")
        self.generic_visit(node)


def lint_source(source: str, relpath: str, cfg: Optional[LintConfig] = None,
                report: Optional[Report] = None) -> Report:
    report = report if report is not None else Report()
    cfg = cfg if cfg is not None else LintConfig()
    tree = ast.parse(source, filename=relpath)
    _FileLinter(relpath, source, cfg, report).visit(tree)
    return report


def lint_tree(root: str, cfg: Optional[LintConfig] = None) -> Report:
    """Lint every ``.py`` under ``root`` (the ``src/repro`` package)."""
    cfg = cfg if cfg is not None else LintConfig()
    report = Report()
    rootp = Path(root)
    for path in sorted(rootp.rglob("*.py")):
        rel = path.relative_to(rootp).as_posix()
        if re.search(cfg.skip_re, rel):
            continue
        lint_source(path.read_text(), rel, cfg, report)
    counts: Dict[str, int] = {}
    for f in report.findings:
        if not f.suppressed:
            counts[f.rule] = counts.get(f.rule, 0) + 1
    report.metrics["lint.files"] = sum(1 for _ in rootp.rglob("*.py"))
    report.metrics["lint.findings_by_rule"] = counts
    return report
