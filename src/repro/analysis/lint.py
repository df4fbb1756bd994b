"""AST lint for repo rules ruff cannot express.

* ``LNT001`` — host numpy / Python RNG calls inside a jit-staged
  function (one decorated with ``jax.jit`` / a ``functools.partial``
  of it, or a function passed to ``shard_map``). Host calls inside a
  staged function either leak a tracer or silently bake a host value
  into the compiled program. Dtype constructors (``np.int32(...)``,
  ``np.iinfo``...) are concrete compile-time constants and stay legal.
* ``LNT002`` — a ``shard_map`` call without an explicit ``check=``
  keyword (``repro.dist.compat.shard_map``): the replication check is
  a reviewed choice per site, and the collectives pass keys its
  allowlist on it.
* ``LNT003`` — ``.item()`` / ``jax.device_get`` in the serve-dispatch
  hot path (``src/repro/serve``): a device sync per request melts the
  batched dispatch throughput the serve tier exists to provide.
"""

from __future__ import annotations

import ast
import os
from typing import Iterator, List, Optional, Set

from .findings import REPO_ROOT, Finding, Report, rel_to_repo

# np.<attr> calls that are compile-time constants, legal under jit
_NP_CONST_ATTRS = {
    "int8",
    "int16",
    "int32",
    "int64",
    "uint8",
    "uint16",
    "uint32",
    "uint64",
    "float16",
    "float32",
    "float64",
    "bool_",
    "dtype",
    "iinfo",
    "finfo",
}
_SERVE_HOT_PREFIXES = ("src/repro/serve/",)
_SKIP_PARTS = ("/fixtures/", "/tests/", "/__pycache__/")


def _attr_root(node: ast.AST) -> Optional[str]:
    """Leftmost name of an attribute chain (``np.random.x`` -> np)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _attr_chain(node: ast.AST) -> List[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return list(reversed(parts))


def _decorator_mentions_jit(dec: ast.AST) -> bool:
    for node in ast.walk(dec):
        if isinstance(node, ast.Name) and node.id == "jit":
            return True
        if isinstance(node, ast.Attribute) and node.attr == "jit":
            return True
    return False


def _iter_calls(tree: ast.AST) -> Iterator[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node


def _shard_map_body_names(tree: ast.AST) -> Set[str]:
    """Names of functions passed as the body of a shard_map call."""
    names: Set[str] = set()
    for call in _iter_calls(tree):
        chain = _attr_chain(call.func)
        if not chain or chain[-1] != "shard_map":
            continue
        if call.args and isinstance(call.args[0], ast.Name):
            names.add(call.args[0].id)
    return names


def _staged_functions(tree: ast.AST) -> List[ast.FunctionDef]:
    """Functions whose bodies are staged (jitted or shard_map bodies)."""
    body_names = _shard_map_body_names(tree)
    staged: List[ast.FunctionDef] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        if any(_decorator_mentions_jit(d) for d in node.decorator_list):
            staged.append(node)
        elif node.name in body_names:
            staged.append(node)
    return staged


def _check_staged_fn(
    fn: ast.FunctionDef, file: str, report: Report
) -> None:
    for call in _iter_calls(fn):
        chain = _attr_chain(call.func)
        if len(chain) < 2:
            continue
        root = chain[0]
        if root in ("np", "numpy"):
            if chain[1] == "random" or (
                len(chain) == 2 and chain[1] not in _NP_CONST_ATTRS
            ):
                report.add(
                    Finding(
                        rule="LNT001",
                        pass_name="lint",
                        message=(
                            f"host call {'.'.join(chain)}() inside "
                            f"jit-staged function {fn.name!r}"
                        ),
                        file=file,
                        line=call.lineno,
                        function=fn.name,
                    )
                )
        elif root == "random":
            report.add(
                Finding(
                    rule="LNT001",
                    pass_name="lint",
                    message=(
                        f"Python RNG {'.'.join(chain)}() inside "
                        f"jit-staged function {fn.name!r}"
                    ),
                    file=file,
                    line=call.lineno,
                    function=fn.name,
                )
            )


def _enclosing_function(
    tree: ast.AST, target: ast.AST
) -> str:
    """Name of the innermost FunctionDef containing ``target``."""
    best = ""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(child is target for child in ast.walk(node)):
                best = node.name
    return best


def check_file(
    path: str,
    report: Report,
    serve_hot: Optional[bool] = None,
) -> None:
    """Run all lint rules over one file."""
    file = rel_to_repo(path)
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    tree = ast.parse(source, filename=path)
    if serve_hot is None:
        serve_hot = file.startswith(_SERVE_HOT_PREFIXES)

    for fn in _staged_functions(tree):
        _check_staged_fn(fn, file, report)

    for call in _iter_calls(tree):
        chain = _attr_chain(call.func)
        if chain and chain[-1] == "shard_map":
            kw_names = {kw.arg for kw in call.keywords}
            if "check" not in kw_names:
                report.add(
                    Finding(
                        rule="LNT002",
                        pass_name="lint",
                        message=(
                            "shard_map call without an explicit "
                            "check= keyword"
                        ),
                        file=file,
                        line=call.lineno,
                        function=_enclosing_function(tree, call),
                    )
                )
        if serve_hot and chain:
            hot = None
            if chain[-1] == "item" and isinstance(
                call.func, ast.Attribute
            ):
                hot = ".item()"
            elif chain[-1] == "device_get":
                hot = "device_get"
            if hot:
                report.add(
                    Finding(
                        rule="LNT003",
                        pass_name="lint",
                        message=(
                            f"{hot} in the serve-dispatch hot path "
                            "forces a device sync per request"
                        ),
                        file=file,
                        line=call.lineno,
                        function=_enclosing_function(tree, call),
                    )
                )


def repo_files() -> List[str]:
    """Python files the lint pass covers (src/repro, launch incl.)."""
    roots = [os.path.join(REPO_ROOT, "src", "repro")]
    files: List[str] = []
    for root in roots:
        for dirpath, _, names in os.walk(root):
            for name in sorted(names):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                norm = "/" + rel_to_repo(path) + "/"
                if any(part in norm for part in _SKIP_PARTS):
                    continue
                files.append(path)
    return files


def run(report: Report, files: Optional[List[str]] = None) -> int:
    targets = files if files is not None else repo_files()
    for path in targets:
        check_file(path, report)
    return len(targets)
