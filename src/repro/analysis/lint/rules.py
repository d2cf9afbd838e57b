"""fedlint layer 1: the AST rule engine (rules FED001-FED006).

The engine builds a *project-wide* picture before any rule fires:

  1. every ``.py`` file is parsed once into a :class:`ModuleInfo`
     (functions with qualnames, per-module import aliases, class
     method tables);
  2. **traced roots** are collected — functions that jax will trace:
     ``@jax.jit`` / ``functools.partial(jax.jit, ...)`` decorations,
     functions passed to ``jax.jit`` / ``vmap`` / ``grad`` /
     ``lax.scan`` / ``lax.cond`` / ``shard_map`` /
     ``pl.pallas_call`` / ``jax.checkpoint`` call sites, Pallas kernel
     bodies (``*_ref`` parameter convention), and functions nested
     inside any of those;
  3. traced-ness propagates over the *cross-module* call graph
     (``from repro.fl.batch_engine import chunk_round_program`` inside
     a jitted body makes ``chunk_round_program`` traced too), stopping
     at host-callback boundaries: a callee handed to
     ``jax.pure_callback`` / ``io_callback`` runs host-side and is
     exempt from the traced-body rules.

Rules (see docs/analysis.md for the catalog with examples):

  FED001  host RNG (``np.random`` / stdlib ``random``) reachable from
          a traced body — silently constant-folds at trace time.
  FED002  implicit host sync (``.item()``, ``float()`` / ``int()`` /
          ``bool()`` on non-shape values, ``np.asarray`` /
          ``np.array``) inside a traced body.
  FED003  ``static_argnames`` / ``static_argnums`` entries must name
          real parameters of the wrapped function.
  FED004  donated arguments must not be read again after the jitted
          call site in the enclosing scope.
  FED005  ``jax.pure_callback`` callees must have stable identity
          (module-level function, bound method) — lambdas, nested
          defs and inline ``functools.partial`` retrace per call.
  FED006  iteration over unordered ``set`` expressions when building
          collections — param-tree key order must be deterministic.

The resolution is heuristic (names, not types) but repo-shaped: it is
tuned to how this codebase spells its tracing constructs, and the
committed baseline absorbs the rare intentional hit.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

RULES = {
    "FED001": "host RNG reachable from a traced function body",
    "FED002": "implicit host sync on a traced value inside a jitted body",
    "FED003": "static_argnames/static_argnums entry names no real parameter",
    "FED004": "donated argument referenced after the jitted call site",
    "FED005": "pure_callback callee must be module-level / stable identity",
    "FED006": "dict/tree built by iterating an unordered set",
    "FED007": "dead relative link in markdown docs",
}

# call heads that trace their first function-valued argument
_JIT_HEADS = ("jax.jit", "jit", "pjit", "jax.pmap", "pmap")
_TRACE_ARG0_HEADS = (
    "jax.vmap", "vmap", "jax.grad", "jax.value_and_grad", "jax.checkpoint",
    "jax.remat", "jax.custom_vjp", "jax.custom_jvp", "jax.linearize",
    "jax.jacfwd", "jax.jacrev", "jax.hessian",
)
_LAX_HEADS = (
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch", "jax.lax.map", "lax.map",
    "jax.lax.associative_scan", "lax.associative_scan",
)
_SHARD_HEADS = ("shard_map", "jax.shard_map")
_PALLAS_HEADS = ("pl.pallas_call", "pallas_call",
                 "jax.experimental.pallas.pallas_call")
_CALLBACK_HEADS = ("jax.pure_callback", "pure_callback",
                   "jax.experimental.io_callback", "io_callback",
                   "jax.debug.callback")
_PARTIAL_HEADS = ("functools.partial", "partial")
_STATIC_KW_HEADS = _JIT_HEADS + ("jax.checkpoint", "jax.remat", "checkpoint")

_TRACING_HEADS = (_JIT_HEADS + _TRACE_ARG0_HEADS + _LAX_HEADS + _SHARD_HEADS
                  + _PALLAS_HEADS)


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str      # repo-relative posix path
    line: int
    col: int
    symbol: str    # enclosing function qualname ('<module>' at top level)
    message: str
    snippet: str   # stripped source line the finding anchors to

    @property
    def key(self) -> str:
        """Line-number-independent identity used by the baseline file."""
        return "::".join((self.rule, self.path, self.symbol,
                          " ".join(self.snippet.split())))

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.symbol}] {self.message}\n    {self.snippet}")


@dataclass
class FuncInfo:
    module: str
    qualname: str
    node: ast.AST                      # FunctionDef / AsyncFunctionDef / Lambda
    pos_params: List[str]
    kwonly_params: List[str]
    has_varargs: bool
    parent_class: Optional[str]
    parent_func: Optional[str]         # enclosing function qualname (nested)
    traced: bool = False
    host_cb: bool = False
    trace_reason: str = ""


@dataclass
class ModuleInfo:
    name: str                          # dotted module name
    path: Path
    rel: str                           # repo-relative posix path
    tree: ast.Module
    lines: List[str]
    # local name -> ("module", dotted) | ("symbol", dotted_module, symbol)
    imports: Dict[str, Tuple] = field(default_factory=dict)
    functions: Dict[str, FuncInfo] = field(default_factory=dict)
    toplevel: Dict[str, str] = field(default_factory=dict)     # name -> qualname
    methods: Dict[str, Dict[str, str]] = field(default_factory=dict)
    parents: Dict[int, ast.AST] = field(default_factory=dict)  # id(node) -> parent
    func_of_node: Dict[int, str] = field(default_factory=dict)

    def line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _unwrap_partial(call_arg: ast.AST) -> ast.AST:
    """functools.partial(f, ...) -> f (one level is enough here)."""
    if (isinstance(call_arg, ast.Call)
            and dotted(call_arg.func) in _PARTIAL_HEADS and call_arg.args):
        return call_arg.args[0]
    return call_arg


def _literal(node: Optional[ast.AST]):
    if node is None:
        return None
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


class Project:
    """Parsed view of every source file; runs the rules."""

    def __init__(self, files: Iterable[Path], repo_root: Path,
                 src_root: Optional[Path] = None):
        self.repo_root = Path(repo_root)
        self.src_root = Path(src_root) if src_root else self.repo_root / "src"
        self.modules: Dict[str, ModuleInfo] = {}
        self.errors: List[Finding] = []
        for f in sorted(set(Path(p) for p in files)):
            self._load(f)
        self._collect_roots()
        self._propagate()

    # -------------------------------------------------------------- loading
    def _module_name(self, path: Path) -> str:
        try:
            rel = path.resolve().relative_to(self.src_root.resolve())
            parts = list(rel.with_suffix("").parts)
            if parts and parts[-1] == "__init__":
                parts = parts[:-1]
            return ".".join(parts)
        except ValueError:
            return path.stem

    def _load(self, path: Path):
        try:
            src = path.read_text()
            tree = ast.parse(src)
        except (OSError, SyntaxError) as e:
            rel = self._rel(path)
            self.errors.append(Finding(
                "PARSE", rel, getattr(e, "lineno", 0) or 0, 0, "<module>",
                f"cannot parse: {e}", ""))
            return
        mod = ModuleInfo(self._module_name(path), path, self._rel(path),
                         tree, src.splitlines())
        self._index(mod)
        self.modules[mod.name] = mod

    def _rel(self, path: Path) -> str:
        try:
            return path.resolve().relative_to(
                self.repo_root.resolve()).as_posix()
        except ValueError:
            return path.as_posix()

    def _index(self, mod: ModuleInfo):
        for node in ast.walk(mod.tree):
            for child in ast.iter_child_nodes(node):
                mod.parents[id(child)] = node
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    mod.imports[a.asname or a.name.split(".")[0]] = (
                        "module", a.name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    if a.name == "*":
                        continue
                    mod.imports[a.asname or a.name] = (
                        "symbol", node.module, a.name)
        self._index_funcs(mod, mod.tree, cls=None, func=None)

    def _index_funcs(self, mod: ModuleInfo, node: ast.AST,
                     cls: Optional[str], func: Optional[str]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if func:
                    q = f"{func}.<locals>.{child.name}"
                elif cls:
                    q = f"{cls}.{child.name}"
                else:
                    q = child.name
                a = child.args
                pos = [p.arg for p in a.posonlyargs + a.args]
                fi = FuncInfo(mod.name, q, child, pos,
                              [p.arg for p in a.kwonlyargs],
                              a.vararg is not None, cls, func)
                mod.functions[q] = fi
                if func is None and cls is None:
                    mod.toplevel[child.name] = q
                if func is None and cls is not None:
                    mod.methods.setdefault(cls, {})[child.name] = q
                for sub in ast.walk(child):
                    mod.func_of_node.setdefault(id(sub), q)
                self._index_funcs(mod, child, cls=cls, func=q)
            elif isinstance(child, ast.ClassDef):
                if cls is None and func is None:
                    self._index_funcs(mod, child, cls=child.name, func=None)
                else:
                    self._index_funcs(mod, child, cls=cls, func=func)
            elif isinstance(child, ast.Lambda):
                pass   # lambdas handled at their use sites
            else:
                self._index_funcs(mod, child, cls=cls, func=func)

    # --------------------------------------------------------- resolution
    def _head(self, mod: ModuleInfo, expr: ast.AST) -> Optional[str]:
        """Dotted head of a call target, normalizing import aliases of
        plain modules (``import jax.numpy as jnp`` keeps its alias —
        rules match on the common spellings instead)."""
        return dotted(expr)

    def resolve(self, mod: ModuleInfo, ctx_func: Optional[str],
                expr: ast.AST) -> Optional[Tuple[str, str]]:
        """Resolve a function-valued expression to (module, qualname)."""
        expr = _unwrap_partial(expr)
        if isinstance(expr, ast.Name):
            name = expr.id
            # nested defs of the enclosing function chain, innermost out
            q = ctx_func
            while q:
                cand = f"{q}.<locals>.{name}"
                if cand in mod.functions:
                    return (mod.name, cand)
                q = mod.functions[q].parent_func if q in mod.functions else None
            if name in mod.toplevel:
                return (mod.name, mod.toplevel[name])
            imp = mod.imports.get(name)
            if imp and imp[0] == "symbol":
                target = self.modules.get(imp[1])
                if target and imp[2] in target.toplevel:
                    return (target.name, target.toplevel[imp[2]])
            return None
        if isinstance(expr, ast.Attribute):
            base = expr.value
            if isinstance(base, ast.Name):
                if base.id == "self" and ctx_func and ctx_func in mod.functions:
                    cls = mod.functions[ctx_func].parent_class
                    if cls and expr.attr in mod.methods.get(cls, {}):
                        return (mod.name, mod.methods[cls][expr.attr])
                imp = mod.imports.get(base.id)
                if imp and imp[0] == "module":
                    target = self.modules.get(imp[1])
                    if target and expr.attr in target.toplevel:
                        return (target.name, target.toplevel[expr.attr])
                if imp and imp[0] == "symbol":
                    # `from repro.core import parameterization as param_lib`
                    target = self.modules.get(f"{imp[1]}.{imp[2]}")
                    if target and expr.attr in target.toplevel:
                        return (target.name, target.toplevel[expr.attr])
        return None

    def func(self, ref: Tuple[str, str]) -> Optional[FuncInfo]:
        mod = self.modules.get(ref[0])
        return mod.functions.get(ref[1]) if mod else None

    # ----------------------------------------------------- traced roots
    def _mark(self, ref: Optional[Tuple[str, str]], reason: str,
              host_cb: bool = False):
        fi = self.func(ref) if ref else None
        if fi is None:
            return
        if host_cb:
            fi.host_cb = True
        elif not fi.traced:
            fi.traced = True
            fi.trace_reason = reason

    def _collect_roots(self):
        for mod in self.modules.values():
            # decorator roots
            for fi in mod.functions.values():
                node = fi.node
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                for dec in node.decorator_list:
                    head = dotted(dec) or (dotted(dec.func)
                                           if isinstance(dec, ast.Call)
                                           else None)
                    if head in _JIT_HEADS + _TRACE_ARG0_HEADS + _SHARD_HEADS:
                        self._mark((mod.name, fi.qualname), f"@{head}")
                    elif (head in _PARTIAL_HEADS and isinstance(dec, ast.Call)
                          and dec.args):
                        inner = dotted(dec.args[0])
                        if inner in (_JIT_HEADS + _TRACE_ARG0_HEADS
                                     + _SHARD_HEADS):
                            self._mark((mod.name, fi.qualname),
                                       f"@partial({inner})")
                # pallas kernel-body convention: *_ref parameters
                refs = [p for p in fi.pos_params if p.endswith("_ref")]
                if len(refs) >= 2:
                    self._mark((mod.name, fi.qualname), "pallas kernel body")
            # call-site roots
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                head = dotted(node.func)
                if head is None:
                    continue
                ctx = mod.func_of_node.get(id(node))
                if head in _CALLBACK_HEADS:
                    if node.args:
                        self._mark(self.resolve(mod, ctx, node.args[0]),
                                   "host callback", host_cb=True)
                    continue
                if head in _JIT_HEADS + _TRACE_ARG0_HEADS + _SHARD_HEADS \
                        + _PALLAS_HEADS:
                    if node.args:
                        self._mark(self.resolve(mod, ctx, node.args[0]),
                                   f"passed to {head}")
                elif head in _LAX_HEADS:
                    for a in node.args:
                        self._mark(self.resolve(mod, ctx, a),
                                   f"passed to {head}")

    def _propagate(self):
        # call + containment edges, then BFS from the traced roots
        edges: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}
        for mod in self.modules.values():
            for fi in mod.functions.values():
                src = (mod.name, fi.qualname)
                out = edges.setdefault(src, set())
                for node in ast.walk(fi.node):
                    if mod.func_of_node.get(id(node)) != fi.qualname:
                        continue   # body of a nested def — its own node
                    if isinstance(node, ast.Call):
                        ref = self.resolve(mod, fi.qualname, node.func)
                        if ref:
                            out.add(ref)
                # containment: nested defs trace with their parent
                for q, sub in mod.functions.items():
                    if sub.parent_func == fi.qualname:
                        out.add((mod.name, q))
        work = [(m.name, f.qualname) for m in self.modules.values()
                for f in m.functions.values() if f.traced]
        seen = set(work)
        while work:
            src = work.pop()
            for dst in edges.get(src, ()):
                fi = self.func(dst)
                if fi is None or fi.host_cb or dst in seen:
                    continue
                seen.add(dst)
                if not fi.traced:
                    fi.traced = True
                    fi.trace_reason = f"called from {src[1]}"
                work.append(dst)

    # ------------------------------------------------------------- rules
    def run(self, select: Optional[Set[str]] = None) -> List[Finding]:
        findings: List[Finding] = list(self.errors)
        for mod in self.modules.values():
            for fi in mod.functions.values():
                if fi.traced and not fi.host_cb:
                    findings += self._fed001(mod, fi)
                    findings += self._fed002(mod, fi)
            findings += self._fed003(mod)
            findings += self._fed004(mod)
            findings += self._fed005(mod)
            findings += self._fed006(mod)
        if select:
            findings = [f for f in findings if f.rule in select]
        findings.sort(key=lambda f: (f.path, f.line, f.rule))
        return findings

    def _own_nodes(self, mod: ModuleInfo, fi: FuncInfo):
        """Nodes belonging to this function body, not to nested defs."""
        for node in ast.walk(fi.node):
            if mod.func_of_node.get(id(node)) == fi.qualname:
                yield node

    def _mk(self, mod: ModuleInfo, node: ast.AST, rule: str, symbol: str,
            msg: str) -> Finding:
        return Finding(rule, mod.rel, node.lineno, node.col_offset, symbol,
                       msg, mod.line(node.lineno))

    # FED001 — host RNG inside traced bodies
    def _fed001(self, mod: ModuleInfo, fi: FuncInfo) -> List[Finding]:
        np_aliases = {n for n, imp in mod.imports.items()
                      if imp == ("module", "numpy")}
        np_aliases.add("numpy")
        rand_aliases = {n for n, imp in mod.imports.items()
                        if imp == ("module", "random")}
        out = []
        for node in self._own_nodes(mod, fi):
            if not isinstance(node, (ast.Call, ast.Attribute)):
                continue
            head = dotted(node.func if isinstance(node, ast.Call) else node)
            if not head:
                continue
            parts = head.split(".")
            if (len(parts) >= 2 and parts[0] in np_aliases
                    and parts[1] == "random" and isinstance(node, ast.Call)):
                out.append(self._mk(
                    mod, node, "FED001", fi.qualname,
                    f"host RNG `{head}` inside traced body "
                    f"({fi.trace_reason}); use jax.random"))
            elif (parts[0] in rand_aliases and len(parts) == 2
                  and isinstance(node, ast.Call)):
                out.append(self._mk(
                    mod, node, "FED001", fi.qualname,
                    f"stdlib RNG `{head}` inside traced body "
                    f"({fi.trace_reason}); use jax.random"))
        return out

    # FED002 — implicit host sync inside traced bodies
    @staticmethod
    def _shape_like(node: ast.AST) -> bool:
        """True when the expression only touches static shape metadata."""
        names = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in (
                    "shape", "ndim", "size", "dtype", "itemsize", "nbytes"):
                return True
            if (isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name)
                    and sub.func.id == "len"):
                return True
            if isinstance(sub, ast.Name):
                names = True
        return not names   # pure-constant arithmetic is static

    @staticmethod
    def _static_scalar_expr(node: ast.AST, fi: FuncInfo) -> bool:
        """True when every Name leaf is a parameter annotated with a
        Python scalar type (int/float/bool) — such values are static by
        the function's own contract, so float()/int() on them is not a
        sync. Calls other than min/max/abs/round/len disqualify."""
        static = set()
        fnode = fi.node
        if isinstance(fnode, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = fnode.args
            for p in (*a.posonlyargs, *a.args, *a.kwonlyargs):
                ann = p.annotation
                if isinstance(ann, ast.Name) and ann.id in (
                        "int", "float", "bool", "str"):
                    static.add(p.arg)
        if not static:
            return False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                if sub.id not in static and sub.id not in (
                        "min", "max", "abs", "round", "len"):
                    return False
            elif isinstance(sub, ast.Attribute):
                return False
        return True

    def _fed002(self, mod: ModuleInfo, fi: FuncInfo) -> List[Finding]:
        np_aliases = {n for n, imp in mod.imports.items()
                      if imp == ("module", "numpy")}
        np_aliases.add("numpy")
        out = []
        for node in self._own_nodes(mod, fi):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                out.append(self._mk(
                    mod, node, "FED002", fi.qualname,
                    ".item() forces a device sync inside a traced body"))
                continue
            head = dotted(node.func)
            if head and "." in head:
                base, attr = head.rsplit(".", 1)
                if base in np_aliases and attr in ("asarray", "array"):
                    out.append(self._mk(
                        mod, node, "FED002", fi.qualname,
                        f"`{head}` materializes a traced value host-side "
                        f"inside a traced body ({fi.trace_reason})"))
                    continue
            if (isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and len(node.args) == 1
                    and not self._shape_like(node.args[0])
                    and not self._static_scalar_expr(node.args[0], fi)):
                out.append(self._mk(
                    mod, node, "FED002", fi.qualname,
                    f"`{node.func.id}(...)` on a traced value forces a "
                    "host sync (TracerConversionError under jit)"))
        return out

    # FED003 — static_argnames/nums must name real parameters
    def _static_kw_sites(self, mod: ModuleInfo):
        """(call, target FuncInfo) pairs carrying static_* keywords."""
        for node in ast.walk(mod.tree):
            ctx = mod.func_of_node.get(id(node))
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = None
                for q, f in mod.functions.items():
                    if f.node is node:
                        fi = f
                        break
                for dec in node.decorator_list:
                    if not isinstance(dec, ast.Call):
                        continue
                    head = dotted(dec.func)
                    if head in _STATIC_KW_HEADS:
                        yield dec, fi
                    elif head in _PARTIAL_HEADS and dec.args and \
                            dotted(dec.args[0]) in _STATIC_KW_HEADS:
                        yield dec, fi
            elif isinstance(node, ast.Call):
                head = dotted(node.func)
                if head in _STATIC_KW_HEADS and node.args:
                    ref = self.resolve(mod, ctx, node.args[0])
                    if ref:
                        yield node, self.func(ref)

    def _fed003(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        for call, fi in self._static_kw_sites(mod):
            if fi is None:
                continue
            names = _literal(_kw(call, "static_argnames"))
            if isinstance(names, str):
                names = (names,)
            if names:
                valid = set(fi.pos_params) | set(fi.kwonly_params)
                for n in names:
                    if n not in valid:
                        out.append(self._mk(
                            mod, call, "FED003", fi.qualname,
                            f"static_argnames entry {n!r} is not a "
                            f"parameter of {fi.qualname} "
                            f"(has: {', '.join(fi.pos_params)})"))
            nums = _literal(_kw(call, "static_argnums"))
            if isinstance(nums, int):
                nums = (nums,)
            if nums and not fi.has_varargs:
                for i in nums:
                    if not (0 <= int(i) < len(fi.pos_params)):
                        out.append(self._mk(
                            mod, call, "FED003", fi.qualname,
                            f"static_argnums index {i} out of range for "
                            f"{fi.qualname} ({len(fi.pos_params)} "
                            "positional parameters)"))
        return out

    # FED004 — donated buffers must not be read after the call site
    def _donating_bindings(self, mod: ModuleInfo):
        """{binding -> donated positions}: module defs with donate
        decorators plus `X = jax.jit(f, donate_argnums=...)` /
        `self.X = jax.jit(...)` assignments."""
        bindings: Dict[Tuple[str, str], Tuple[int, ...]] = {}

        def donate_of(call: ast.Call) -> Tuple[int, ...]:
            v = _literal(_kw(call, "donate_argnums"))
            if isinstance(v, int):
                v = (v,)
            return tuple(int(i) for i in v) if v else ()

        for fi in mod.functions.values():
            node = fi.node
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fi.parent_func or fi.parent_class:
                continue
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                head = dotted(dec.func)
                target = None
                if head in _JIT_HEADS:
                    target = dec
                elif head in _PARTIAL_HEADS and dec.args and \
                        dotted(dec.args[0]) in _JIT_HEADS:
                    target = dec
                if target is not None:
                    d = donate_of(target)
                    if d:
                        bindings[("name", node.name)] = d
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            val = node.value
            if not (isinstance(val, ast.Call)
                    and dotted(val.func) in _JIT_HEADS):
                continue
            d = _literal(_kw(val, "donate_argnums"))
            if isinstance(d, int):
                d = (d,)
            if not d:
                continue
            t = node.targets[0]
            if isinstance(t, ast.Name):
                bindings[("name", t.id)] = tuple(int(i) for i in d)
            elif (isinstance(t, ast.Attribute)
                  and isinstance(t.value, ast.Name) and t.value.id == "self"):
                bindings[("attr", t.attr)] = tuple(int(i) for i in d)
        return bindings

    def _fed004(self, mod: ModuleInfo) -> List[Finding]:
        bindings = self._donating_bindings(mod)
        if not bindings:
            return []
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = None
            if isinstance(node.func, ast.Name):
                kind = ("name", node.func.id)
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id == "self"):
                kind = ("attr", node.func.attr)
            donated = bindings.get(kind)
            if not donated:
                continue
            ctx = mod.func_of_node.get(id(node))
            if ctx is None or ctx not in mod.functions:
                continue
            fn = mod.functions[ctx].node
            for pos in donated:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                use = self._used_after(mod, fn, node, arg)
                if use is not None:
                    label = (dotted(arg) or
                             getattr(arg, "id", "<expr>"))
                    out.append(self._mk(
                        mod, use, "FED004", ctx,
                        f"donated argument `{label}` (position {pos} of "
                        f"`{kind[1]}`) is read again after the jitted "
                        f"call at line {node.lineno} — its buffer is "
                        "invalid after donation"))
        return out

    @staticmethod
    def _used_after(mod: ModuleInfo, fn: ast.AST, call: ast.Call,
                    arg: ast.AST) -> Optional[ast.AST]:
        """First read of ``arg`` (simple Name or self.X) after ``call``
        inside ``fn`` with no intervening rebind; None if clean."""
        if isinstance(arg, ast.Name):
            def is_load(n):
                return (isinstance(n, ast.Name) and n.id == arg.id
                        and isinstance(n.ctx, ast.Load))

            def is_store(n):
                return (isinstance(n, ast.Name) and n.id == arg.id
                        and isinstance(n.ctx, (ast.Store, ast.Del)))
        elif (isinstance(arg, ast.Attribute)
              and isinstance(arg.value, ast.Name)
              and arg.value.id == "self"):
            def is_load(n):
                return (isinstance(n, ast.Attribute) and n.attr == arg.attr
                        and isinstance(n.value, ast.Name)
                        and n.value.id == "self"
                        and isinstance(n.ctx, ast.Load))

            def is_store(n):
                return (isinstance(n, ast.Attribute) and n.attr == arg.attr
                        and isinstance(n.value, ast.Name)
                        and n.value.id == "self"
                        and isinstance(n.ctx, (ast.Store, ast.Del)))
        else:
            return None   # fresh inline expression: nothing to re-read

        call_end = getattr(call, "end_lineno", call.lineno)
        in_call = {id(n) for n in ast.walk(call)}
        # region of interest: statements after the call; if the call sits
        # in a loop, the whole loop body re-executes, so include it too
        loop_start = None
        cur = mod.parents.get(id(call))
        while cur is not None and cur is not fn:
            if isinstance(cur, (ast.For, ast.While)):
                loop_start = cur.lineno
            cur = mod.parents.get(id(cur))
        loads, stores = [], []
        for n in ast.walk(fn):
            if id(n) in in_call:
                continue
            line = getattr(n, "lineno", None)
            if line is None:
                continue
            after = line > call_end or (loop_start is not None
                                        and line >= loop_start
                                        and line < call.lineno)
            # A store on the call's own line is the assignment target of
            # `x, y = donating_fn(x, y)` — it executes after the call and
            # kills the taint, so collect it even though it isn't "after".
            if is_store(n) and (after or line >= call.lineno):
                stores.append(n)
            elif is_load(n) and after:
                loads.append(n)
        for ld in sorted(loads, key=lambda n: n.lineno):
            if ld.lineno > call_end:
                rebound = any(call.lineno <= s.lineno <= ld.lineno
                              for s in stores)
            else:
                # Loop-prefix read: executes on the *next* iteration, after
                # the call. Killed by any rebind at/after the call or in
                # the prefix before the read.
                rebound = any(s.lineno >= call.lineno
                              or (loop_start is not None
                                  and loop_start <= s.lineno <= ld.lineno)
                              for s in stores)
            if not rebound:
                return ld
        return None

    # FED005 — pure_callback callee identity
    def _fed005(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted(node.func) not in _CALLBACK_HEADS or not node.args:
                continue
            ctx = mod.func_of_node.get(id(node), "<module>")
            cb = node.args[0]
            if isinstance(cb, ast.Lambda):
                out.append(self._mk(
                    mod, node, "FED005", ctx,
                    "pure_callback callee is a lambda — fresh identity "
                    "per call retraces the enclosing program"))
                continue
            if (isinstance(cb, ast.Call)
                    and dotted(cb.func) in _PARTIAL_HEADS):
                out.append(self._mk(
                    mod, node, "FED005", ctx,
                    "pure_callback callee is an inline functools.partial "
                    "— fresh identity per call retraces the program"))
                continue
            ref = self.resolve(mod, ctx if ctx != "<module>" else None, cb)
            fi = self.func(ref) if ref else None
            if fi is not None and fi.parent_func is not None:
                out.append(self._mk(
                    mod, node, "FED005", ctx,
                    f"pure_callback callee `{fi.qualname}` is a nested "
                    "def — a new function object per enclosing call "
                    "retraces the program; hoist it to module level"))
        return out

    # FED006 — iteration over unordered sets
    @staticmethod
    def _is_set_expr(node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("set", "frozenset"):
            return True
        if isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.BitOr, ast.BitAnd, ast.Sub, ast.BitXor)):
            def keysish(n):
                return ((isinstance(n, ast.Call)
                         and isinstance(n.func, ast.Attribute)
                         and n.func.attr == "keys")
                        or Project._is_set_expr(n))
            return keysish(node.left) and keysish(node.right)
        return False

    def _fed006(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        for node in ast.walk(mod.tree):
            ctx = mod.func_of_node.get(id(node), "<module>")
            iters = []
            if isinstance(node, ast.For):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.DictComp, ast.SetComp,
                                   ast.GeneratorExp)):
                iters += [g.iter for g in node.generators]
            for it in iters:
                if self._is_set_expr(it):
                    out.append(self._mk(
                        mod, it, "FED006", ctx,
                        "iterating an unordered set while building a "
                        "collection — wrap in sorted(...) so param-tree "
                        "key order is deterministic"))
        return out
