"""PVT — private-API dependency guard (pins against the installed jax).

The repo leans on private jax internals in exactly two sanctioned ways:
kernel launch forks that call a private Pallas kernel positionally
(``ops/paged_attention_q8.py``), and lazy imports of private library
kernels (megablox gmm, the paged-attention wrapper).
A jax upgrade can silently reorder/extend those signatures — positional
call sites then pass the wrong argument into the wrong parameter with no
error at all. The defense is the pinned-signature idiom: an
``_EXPECTED_*`` tuple of parameter names compared against
``inspect.signature(...)`` at import/first-use (as in
``ops/paged_attention_q8.py``), or the equivalent
``utils.private_api.pin_signature(symbol, _EXPECTED_*)`` helper. A pinned
symbol is a function or a class: a dataclass's signature is its fields.

PVT both enforces the idiom and *executes* it at lint time: every pin on
a ``jax.*`` symbol is checked against the **installed** jax, so signature
drift surfaces as a lint finding with a parameter diff during the jax
bump itself — not as an ImportError (or silent corruption) at serve time.

  PVT001  import from a private jax module (``jax._src`` or
          ``jax.experimental.pallas.ops``) with no pinned-signature
          idiom and no try/except-ImportError gate
  PVT002  pinned ``_EXPECTED_*`` tuple disagrees with the installed
          jax's signature (reported with the added/removed/reordered
          parameter diff — never a crash)
  PVT003  pinned symbol cannot be resolved in the installed jax at all

Imports wrapped in try/except catching ImportError are exempt from
PVT001: they already degrade gracefully (the jax_compat shims). Only
``jax.``-prefixed modules are ever imported by the analyzer — pins on
anything else are left unverified.
"""

from __future__ import annotations

import ast
import importlib
import inspect
from typing import Iterator

from areal_tpu.analysis.core import (
    Finding,
    ProjectContext,
    SourceFile,
    dotted_name,
    make_key,
)

_PRIVATE_PREFIXES = ("jax._src", "jax.experimental.pallas.ops")


def _is_private(module: str | None) -> bool:
    return bool(module) and any(
        module == p or module.startswith(p + ".") for p in _PRIVATE_PREFIXES
    )


def _import_gated(sf: SourceFile, node: ast.AST) -> bool:
    """True when ``node`` sits in a try whose handlers catch ImportError
    (or a superclass) — the graceful-degradation idiom."""
    catching = {"ImportError", "ModuleNotFoundError", "Exception", "BaseException"}
    cur = sf.parents.get(id(node))
    while cur is not None:
        if isinstance(cur, ast.Try):
            for h in cur.handlers:
                types = []
                if h.type is None:
                    return True  # bare except
                if isinstance(h.type, ast.Tuple):
                    types = h.type.elts
                else:
                    types = [h.type]
                for t in types:
                    if (dotted_name(t) or "").split(".")[-1] in catching:
                        return True
        cur = sf.parents.get(id(cur))
    return False


def _signature_symbol(node: ast.expr) -> str | None:
    """NAME inside ``[tuple(]inspect.signature(NAME).parameters[)]``."""
    if isinstance(node, ast.Call) and (
        (dotted_name(node.func) or "").split(".")[-1] == "tuple"
    ):
        node = node.args[0] if node.args else node
    if isinstance(node, ast.Attribute) and node.attr == "parameters":
        node = node.value
    if isinstance(node, ast.Call) and (
        (dotted_name(node.func) or "").split(".")[-1] == "signature"
    ):
        if node.args and isinstance(node.args[0], ast.Name):
            return node.args[0].id
    return None


def _literal_str_tuple(node: ast.expr) -> tuple[str, ...] | None:
    if isinstance(node, (ast.Tuple, ast.List)) and all(
        isinstance(e, ast.Constant) and isinstance(e.value, str)
        for e in node.elts
    ):
        return tuple(e.value for e in node.elts)
    return None


class PrivateApiChecker:
    FAMILY = "PVT"
    RULES = {
        "PVT001": "private jax import without a pinned-signature guard",
        "PVT002": "pinned signature disagrees with the installed jax",
        "PVT003": "pinned private symbol unresolvable in the installed jax",
    }

    def __init__(self) -> None:
        self._module_cache: dict[str, object | Exception] = {}

    def check(self, sf: SourceFile, ctx: ProjectContext) -> Iterator[Finding]:
        # private imports: local name -> (module, original name, node)
        private: dict[str, tuple[str, str, ast.ImportFrom]] = {}
        statements: list[ast.ImportFrom] = []
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ImportFrom) and _is_private(node.module):
                statements.append(node)
                for a in node.names:
                    private[a.asname or a.name] = (node.module, a.name, node)
        if not private:
            return

        pins = self._collect_pins(sf)
        pinned_symbols = {sym for sym, _, _, _ in pins}

        # PVT001: every private import statement must be gated or carry at
        # least one pinned symbol (constants like DEFAULT_MASK_VALUE may
        # ride along with a pinned function from the same module).
        for node in statements:
            if _import_gated(sf, node):
                continue
            names = [a.asname or a.name for a in node.names]
            if any(n in pinned_symbols for n in names):
                continue
            yield Finding(
                rule="PVT001",
                path=sf.relpath,
                line=node.lineno,
                message=(
                    f"import from private `{node.module}` carries no "
                    "pinned-signature guard (`_EXPECTED_*` tuple checked "
                    "via inspect.signature, or "
                    "utils.private_api.pin_signature) and no try/except "
                    "ImportError gate: a jax bump can silently reorder "
                    "its parameters"
                ),
                key=make_key(
                    "PVT001", sf.relpath, sf.scope_of(node), node.module
                ),
            )

        # PVT002/PVT003: execute each pin against the installed jax.
        for sym, expected_name, expected, line in pins:
            if sym not in private:
                continue
            module, orig, _ = private[sym]
            if not module.startswith("jax"):
                continue
            obj, err = self._resolve_symbol(module, orig)
            if obj is None:
                yield Finding(
                    rule="PVT003",
                    path=sf.relpath,
                    line=line,
                    message=(
                        f"pin `{expected_name}` targets "
                        f"`{module}.{orig}` which the installed jax "
                        f"cannot resolve ({err}); the launch fork is "
                        "dead code until re-audited"
                    ),
                    key=make_key(
                        "PVT003", sf.relpath, "<module>", f"{module}.{orig}"
                    ),
                )
                continue
            try:
                got = tuple(inspect.signature(obj).parameters)
            except (TypeError, ValueError) as e:
                yield Finding(
                    rule="PVT003",
                    path=sf.relpath,
                    line=line,
                    message=(
                        f"pin `{expected_name}`: `{module}.{orig}` has no "
                        f"inspectable signature ({e})"
                    ),
                    key=make_key(
                        "PVT003", sf.relpath, "<module>", f"sig:{module}.{orig}"
                    ),
                )
                continue
            if got != expected:
                missing = [p for p in expected if p not in got]
                added = [p for p in got if p not in expected]
                if missing or added:
                    diff = (
                        f"installed jax removed {missing or 'nothing'}, "
                        f"added {added or 'nothing'}"
                    )
                else:
                    diff = f"parameters reordered: installed order is {got}"
                yield Finding(
                    rule="PVT002",
                    path=sf.relpath,
                    line=line,
                    message=(
                        f"pin `{expected_name}` disagrees with the "
                        f"installed `{module}.{orig}`: {diff}; re-audit "
                        "every positional call site, then update the pin"
                    ),
                    key=make_key(
                        "PVT002", sf.relpath, "<module>", expected_name
                    ),
                )

    # -- pin discovery ------------------------------------------------------
    def _collect_pins(
        self, sf: SourceFile
    ) -> list[tuple[str, str, tuple[str, ...], int]]:
        """(symbol, _EXPECTED name, pinned tuple, lineno) for every pin in
        the file, via either idiom:

          _got = tuple(inspect.signature(SYM).parameters)
          if _got != _EXPECTED_X: ...          # comparison idiom
          pin_signature(SYM, _EXPECTED_X)      # helper idiom
        """
        expected: dict[str, tuple[tuple[str, ...], int]] = {}
        sig_of: dict[str, str] = {}  # intermediate var -> pinned symbol
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and (
                isinstance(node.targets[0], ast.Name)
            ):
                name = node.targets[0].id
                if name.startswith("_EXPECTED"):
                    tup = _literal_str_tuple(node.value)
                    if tup is not None:
                        expected[name] = (tup, node.lineno)
                sym = _signature_symbol(node.value)
                if sym is not None:
                    sig_of[name] = sym

        pins: list[tuple[str, str, tuple[str, ...], int]] = []

        def side_symbol(side: ast.expr) -> str | None:
            if isinstance(side, ast.Name) and side.id in sig_of:
                return sig_of[side.id]
            return _signature_symbol(side)

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Compare) and len(node.comparators) == 1:
                sides = (node.left, node.comparators[0])
                exp = next(
                    (
                        s.id
                        for s in sides
                        if isinstance(s, ast.Name) and s.id in expected
                    ),
                    None,
                )
                sym = next(
                    (x for s in sides if (x := side_symbol(s)) is not None),
                    None,
                )
                if exp and sym:
                    pins.append((sym, exp, *expected[exp][:1], expected[exp][1]))
            elif isinstance(node, ast.Call) and (
                (dotted_name(node.func) or "").split(".")[-1]
                == "pin_signature"
            ):
                if (
                    len(node.args) >= 2
                    and isinstance(node.args[0], ast.Name)
                    and isinstance(node.args[1], ast.Name)
                    and node.args[1].id in expected
                ):
                    exp = node.args[1].id
                    pins.append(
                        (node.args[0].id, exp, *expected[exp][:1], expected[exp][1])
                    )
        return pins

    # -- installed-jax resolution -------------------------------------------
    def _resolve_symbol(self, module: str, name: str):
        cached = self._module_cache.get(module)
        if cached is None:
            try:
                cached = importlib.import_module(module)
            except Exception as e:  # noqa: BLE001 — any failure is PVT003
                cached = e
            self._module_cache[module] = cached
        if isinstance(cached, Exception):
            return None, f"import failed: {cached}"
        obj = getattr(cached, name, None)
        if obj is None:
            return None, "attribute missing"
        return obj, None
