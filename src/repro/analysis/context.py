"""Shared AST context for the lint rules.

Every rule consumes a :class:`Project` (the parsed file set) and iterates
:class:`ModuleInfo` objects. The helpers here centralize the repo's JAX
idioms so rules stay declarative:

* alias-resolved dotted names (``import jax.numpy as jnp`` makes
  ``jnp.concatenate`` resolve to ``jax.numpy.concatenate``);
* jit-context discovery — decorator forms (``@jax.jit``,
  ``@partial(jax.jit, ...)``), wrapper assignments/returns
  (``f = jax.jit(g)``, ``return jax.jit(solve)``) and control-flow bodies
  handed to ``lax.while_loop`` / ``scan`` / ``fori_loop`` / ``cond`` — all
  of which trace their function arguments;
* shard_map decoration parsing (mesh/in_specs/out_specs kwargs).

Nothing here imports JAX: the analyzer must run (and fail fast) even in an
environment where the runtime can't.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import PurePosixPath
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

#: canonical module names whose presence marks a module as mesh-aware
MESH_IMPORT_ROOTS = (
    "jax.sharding",
    "jax.experimental.shard_map",
    "repro.sharding",
)

#: names that, when imported, mark a module as mesh-aware
MESH_IMPORT_NAMES = {"Mesh", "NamedSharding", "PartitionSpec", "shard_map"}

#: lax control-flow entry points whose function args are traced
_TRACED_HOF = {
    "jax.lax.while_loop": (0, 1),
    "jax.lax.scan": (0,),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2, 3),
    "jax.lax.switch": None,   # every arg past the index may be a branch
    "jax.lax.map": (0,),
}


def qualname(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Dotted name of an expression, alias-resolved to canonical roots.

    ``jnp.concatenate`` -> ``jax.numpy.concatenate`` when the module did
    ``import jax.numpy as jnp``; plain names resolve through ``from x
    import y [as z]``. Returns None for non-name expressions.
    """
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None
    head = aliases.get(cur.id, cur.id)
    parts.append(head)
    return ".".join(reversed(parts))


@dataclass
class ShardMapDecoration:
    """A parsed ``shard_map`` application site."""

    node: ast.Call                      # the shard_map(...) / partial(...) call
    in_specs: Optional[ast.expr]
    out_specs: Optional[ast.expr]
    line: int


@dataclass
class ModuleInfo:
    path: str                           # posix path relative to repo root
    source: str
    tree: ast.Module
    lines: List[str]
    aliases: Dict[str, str] = field(default_factory=dict)
    imported_modules: Set[str] = field(default_factory=set)
    _jit_functions: Optional[Set[ast.FunctionDef]] = None

    # -- imports ------------------------------------------------------------

    @classmethod
    def parse(cls, path: str, source: str) -> "ModuleInfo":
        tree = ast.parse(source, filename=path)
        info = cls(path=PurePosixPath(path).as_posix(), source=source,
                   tree=tree, lines=source.splitlines())
        info._collect_imports()
        return info

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
                    self.imported_modules.add(a.name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                self.imported_modules.add(node.module)
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}"
                    )

    def qualname(self, node: ast.AST) -> Optional[str]:
        return qualname(node, self.aliases)

    @property
    def imports_jax(self) -> bool:
        return any(m == "jax" or m.startswith("jax.")
                   for m in self.imported_modules)

    @property
    def mesh_context(self) -> bool:
        """Mesh-aware module: imports sharding machinery (the contexts in
        which a stray ``jnp.concatenate`` can hit the P(model)-concat
        miscompile this repo guards against in ``sharding/collect.py``)."""
        for m in self.imported_modules:
            if any(m == r or m.startswith(r + ".") for r in MESH_IMPORT_ROOTS):
                return True
        resolved = set(self.aliases.values())
        return any(
            r.rsplit(".", 1)[-1] in MESH_IMPORT_NAMES and "." in r
            and r.rsplit(".", 1)[0].startswith(("jax", "repro"))
            for r in resolved
        )

    # -- function scopes ----------------------------------------------------

    def functions(self) -> Iterator[ast.FunctionDef]:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    def jit_functions(self) -> Set[ast.FunctionDef]:
        """Function defs whose bodies run under trace: jit-decorated,
        jit-wrapped by name, or passed to lax control flow. Includes
        functions *nested inside* such functions (the whole body traces).
        """
        if self._jit_functions is not None:
            return self._jit_functions
        by_name: Dict[str, List[ast.FunctionDef]] = {}
        for fn in self.functions():
            by_name.setdefault(fn.name, []).append(fn)
        traced: Set[ast.FunctionDef] = set()

        def is_jit_call(call: ast.Call) -> bool:
            q = self.qualname(call.func)
            if q in ("jax.jit", "jit", "jax.pmap", "jax.vmap"):
                return True
            if q in ("functools.partial", "partial") and call.args:
                return self.qualname(call.args[0]) in ("jax.jit", "jit")
            return False

        for fn in self.functions():
            for dec in fn.decorator_list:
                q = self.qualname(dec)
                if q in ("jax.jit", "jit"):
                    traced.add(fn)
                elif isinstance(dec, ast.Call) and is_jit_call(dec):
                    traced.add(fn)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            q = self.qualname(node.func)
            if q in ("jax.jit", "jit"):
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name):
                        traced.update(by_name.get(arg.id, ()))
            elif q in _TRACED_HOF:
                idxs = _TRACED_HOF[q]
                args = (node.args if idxs is None
                        else [node.args[i] for i in idxs
                              if i < len(node.args)])
                for arg in args:
                    if isinstance(arg, ast.Name):
                        traced.update(by_name.get(arg.id, ()))
        # close over nesting: any def lexically inside a traced def traces
        changed = True
        while changed:
            changed = False
            for fn in list(traced):
                for inner in ast.walk(fn):
                    if (isinstance(inner, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))
                            and inner is not fn and inner not in traced):
                        traced.add(inner)
                        changed = True
        self._jit_functions = traced
        return traced

    # -- shard_map ----------------------------------------------------------

    def shard_map_decorations(
        self,
    ) -> Iterator[Tuple[ast.FunctionDef, ShardMapDecoration]]:
        """(fn, decoration) for every def decorated with shard_map —
        directly or through ``partial(shard_map, ...)``."""
        for fn in self.functions():
            for dec in fn.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                q = self.qualname(dec.func)
                target = None
                if q is not None and q.endswith("shard_map"):
                    target = dec
                elif (q in ("functools.partial", "partial") and dec.args):
                    inner_q = self.qualname(dec.args[0])
                    if inner_q is not None and inner_q.endswith("shard_map"):
                        target = dec
                if target is None:
                    continue
                kw = {k.arg: k.value for k in target.keywords if k.arg}
                yield fn, ShardMapDecoration(
                    node=target, in_specs=kw.get("in_specs"),
                    out_specs=kw.get("out_specs"), line=target.lineno,
                )

    def declared_axis_names(self) -> Set[str]:
        """Axis-name string literals declared by this module's sharding
        constructs: ``P(...)`` / ``PartitionSpec(...)`` entries, Mesh
        ``axis_names``, and defaults of ``*_axis`` parameters."""
        out: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                q = self.qualname(node.func)
                if q is None:
                    continue
                tail = q.rsplit(".", 1)[-1]
                if tail in ("PartitionSpec", "P"):
                    for arg in list(node.args) + [
                            k.value for k in node.keywords]:
                        out.update(_string_leaves(arg))
                elif tail in ("Mesh", "make_mesh", "make_dev_mesh"):
                    for k in node.keywords:
                        if k.arg == "axis_names":
                            out.update(_string_leaves(k.value))
                    for arg in node.args:
                        out.update(_string_leaves(arg))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                pos = args.posonlyargs + args.args
                defaults = list(args.defaults)
                for a, d in zip(pos[len(pos) - len(defaults):], defaults):
                    if a.arg.endswith("_axis"):
                        out.update(_string_leaves(d))
                for a, d in zip(args.kwonlyargs, args.kw_defaults):
                    if d is not None and a.arg.endswith("_axis"):
                        out.update(_string_leaves(d))
        return out


def _string_leaves(node: Optional[ast.AST]) -> Iterator[str]:
    if node is None:
        return
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n.value


def positional_param_count(fn: ast.FunctionDef) -> int:
    return len(fn.args.posonlyargs) + len(fn.args.args)


def spec_tuple_len(spec: ast.expr) -> Optional[int]:
    """Length of a literal in_specs tuple/list; 1 for a single P(...);
    None when the expression is dynamic (a variable, a comprehension)."""
    if isinstance(spec, (ast.Tuple, ast.List)):
        return len(spec.elts)
    if isinstance(spec, ast.Call):
        return 1
    return None


@dataclass
class Project:
    root: str
    modules: List[ModuleInfo]

    def by_path(self, suffix: str) -> Optional[ModuleInfo]:
        for m in self.modules:
            if m.path.endswith(suffix):
                return m
        return None

    def iter_modules(
        self, under: Optional[Sequence[str]] = None
    ) -> Iterator[ModuleInfo]:
        for m in self.modules:
            if under is None or any(m.path.startswith(u) for u in under):
                yield m
