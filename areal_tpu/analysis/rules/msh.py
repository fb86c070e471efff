"""MSH — SPMD/collective consistency against the mesh/axis environment.

Collectives are stringly-typed the same way PartitionSpecs are (SHD):
``jax.lax.psum(x, "modle")`` raises nothing until trace time inside a
real mapped region, and ``shard_map`` out_specs that disagree with the
callee's return structure fail as opaque pytree errors. Worse, a mapped
region's axes are Manual, so a raw ``jax.lax.with_sharding_constraint``
naming one inside a mapped body dies at *lowering* time ("can only refer
to Auto axes of the mesh"), and outside any mesh context it raises too —
the failure that kept tests/test_pp_engine.py red since seed. The fix routes
every constraint through ``utils/jax_compat.with_sharding_constraint``
(which drops manual axes); MSH003 pins that routing so the next
refactor cannot silently reintroduce the raw call.

  MSH001  collective axis name not in the mesh/axis vocabulary
          (package MESH_AXES + file-local MESH_AXES + ad-hoc Mesh
          constructions + pmap/vmap ``axis_name=`` bindings)
  MSH002  shard_map out_specs tuple length differs from the callee's
          literal tuple return (both fully literal; a single spec is a
          legal pytree prefix and is never flagged)
  MSH003  raw ``jax.lax.with_sharding_constraint`` call — it cannot name
          a Manual axis inside shard_map regions; route
          through areal_tpu.utils.jax_compat.with_sharding_constraint

Only names that resolve to jax (``jax.lax.*`` / ``lax.*`` dotted paths,
or bare names imported from a jax module) are checked, so an unrelated
local ``all_gather`` helper never false-positives. Unknown stays silent.
"""

from __future__ import annotations

import ast
from typing import Iterator

from areal_tpu.analysis.core import (
    Finding,
    ProjectContext,
    SourceFile,
    dotted_name,
    make_key,
)
from areal_tpu.analysis.rules.shd import (
    _declared_mesh_axes,
    _local_mesh_axes,
)

_COLLECTIVES = {
    # name -> positional index of the axis-name argument
    "psum": 1,
    "pmean": 1,
    "pmax": 1,
    "pmin": 1,
    "ppermute": 1,
    "pshuffle": 1,
    "psum_scatter": 1,
    "all_gather": 1,
    "all_to_all": 1,
    "axis_index": 0,
    "axis_size": 0,
}


def _jax_bound_names(tree: ast.Module) -> set[str]:
    """Bare local names that resolve into jax (``from jax.lax import
    all_gather``, ``from areal_tpu.utils.jax_compat import axis_size``)."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module.startswith("jax")
            or node.module.endswith("jax_compat")
        ):
            for a in node.names:
                out.add(a.asname or a.name)
    return out


def _axis_names(node: ast.expr | None) -> list[str] | None:
    """Literal axis name(s): "axis" or a tuple/list of them. None = skip."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return out
    return None


def _bound_axis_names(tree: ast.Module) -> set[str]:
    """Axis names bound by pmap/vmap/shard_map-adjacent ``axis_name=``."""
    axes: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for k in node.keywords:
            if k.arg in ("axis_name", "axis_names"):
                got = _axis_names(k.value)
                if got:
                    axes.update(got)
    return axes


class MeshCollectiveChecker:
    FAMILY = "MSH"
    RULES = {
        "MSH001": "collective axis name not in the mesh vocabulary",
        "MSH002": "shard_map out_specs length differs from callee return",
        "MSH003": "raw with_sharding_constraint (manual-axes-unsafe)",
    }

    def check(self, sf: SourceFile, ctx: ProjectContext) -> Iterator[Finding]:
        axes = _local_mesh_axes(sf.tree)
        if axes is None:
            axes = ctx.mesh_axes
        axes = frozenset(
            axes | _declared_mesh_axes(sf.tree) | _bound_axis_names(sf.tree)
        )
        jax_names = _jax_bound_names(sf.tree)
        yield from self._check_collectives(sf, axes, jax_names)
        yield from self._check_out_specs(sf)
        yield from self._check_raw_constraint(sf)

    # -- MSH001 -------------------------------------------------------------
    def _check_collectives(
        self, sf: SourceFile, axes: frozenset[str], jax_names: set[str]
    ) -> Iterator[Finding]:
        if not axes:
            return
        for call in ast.walk(sf.tree):
            if not isinstance(call, ast.Call):
                continue
            d = dotted_name(call.func)
            if d is None:
                continue
            last = d.split(".")[-1]
            if last not in _COLLECTIVES:
                continue
            if "." in d:
                head = d.split(".")[0]
                if head not in ("jax", "lax"):
                    continue
            elif last not in jax_names:
                continue
            arg: ast.expr | None = None
            for k in call.keywords:
                if k.arg == "axis_name":
                    arg = k.value
            if arg is None:
                idx = _COLLECTIVES[last]
                if len(call.args) > idx:
                    arg = call.args[idx]
            names = _axis_names(arg)
            if not names:
                continue
            for axis in names:
                if axis in axes:
                    continue
                yield Finding(
                    rule="MSH001",
                    path=sf.relpath,
                    line=call.lineno,
                    message=(
                        f"collective `{last}` names axis '{axis}' which is "
                        f"not in the mesh/axis vocabulary "
                        f"({', '.join(sorted(axes))}); an unbound axis "
                        "name fails only at trace time inside the mapped "
                        "region"
                    ),
                    key=make_key(
                        "MSH001",
                        sf.relpath,
                        sf.scope_of(call),
                        f"{last}:{axis}",
                    ),
                )

    # -- MSH002 -------------------------------------------------------------
    def _check_out_specs(self, sf: SourceFile) -> Iterator[Finding]:
        local_defs: dict[str, ast.AST] = {}
        assigned: set[str] = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local_defs[node.name] = node
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    for el in t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]:
                        if isinstance(el, ast.Name):
                            assigned.add(el.id)
        for name in assigned:
            local_defs.pop(name, None)
        for call in ast.walk(sf.tree):
            if not isinstance(call, ast.Call):
                continue
            d = dotted_name(call.func)
            if d is None or d.split(".")[-1] != "shard_map":
                continue
            if not call.args:
                continue
            target = call.args[0]
            fn: ast.AST | None = None
            if isinstance(target, ast.Lambda):
                fn = target
            elif isinstance(target, ast.Name):
                fn = local_defs.get(target.id)
            if fn is None:
                continue
            out_specs = next(
                (k.value for k in call.keywords if k.arg == "out_specs"), None
            )
            if out_specs is None and len(call.args) >= 4:
                out_specs = call.args[3]
            if not isinstance(out_specs, (ast.Tuple, ast.List)):
                continue  # single spec = legal pytree prefix
            n_specs = len(out_specs.elts)
            returns: set[int] = set()
            if isinstance(fn, ast.Lambda):
                body = fn.body
                returns.add(
                    len(body.elts) if isinstance(body, ast.Tuple) else 1
                )
            else:
                for node in ast.walk(fn):
                    if isinstance(node, ast.Return) and node.value is not None:
                        v = node.value
                        if isinstance(v, ast.Tuple):
                            returns.add(len(v.elts))
                        elif isinstance(v, (ast.Name, ast.Constant, ast.Call)):
                            returns.add(1)
            if len(returns) != 1:
                continue  # inconsistent/unresolvable returns: skip
            n_ret = returns.pop()
            if n_ret == n_specs:
                continue
            yield Finding(
                rule="MSH002",
                path=sf.relpath,
                line=call.lineno,
                message=(
                    f"shard_map out_specs has {n_specs} entries but "
                    f"`{getattr(fn, 'name', '<lambda>')}` returns "
                    f"{n_ret} value(s); the mismatch fails as an opaque "
                    "pytree-structure error at trace time"
                ),
                key=make_key(
                    "MSH002",
                    sf.relpath,
                    sf.scope_of(call),
                    getattr(fn, "name", "<lambda>"),
                ),
            )

    # -- MSH003 -------------------------------------------------------------
    def _check_raw_constraint(self, sf: SourceFile) -> Iterator[Finding]:
        # bare-name calls count only when imported from jax.lax directly
        raw_names = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ImportFrom) and node.module in (
                "jax.lax",
                "jax.experimental.pjit",
            ):
                for a in node.names:
                    if a.name == "with_sharding_constraint":
                        raw_names.add(a.asname or a.name)
        for call in ast.walk(sf.tree):
            if not isinstance(call, ast.Call):
                continue
            d = dotted_name(call.func)
            if d is None:
                continue
            flagged = d in (
                "jax.lax.with_sharding_constraint",
                "lax.with_sharding_constraint",
            ) or ("." not in d and d in raw_names)
            if not flagged:
                continue
            yield Finding(
                rule="MSH003",
                path=sf.relpath,
                line=call.lineno,
                message=(
                    "raw jax.lax.with_sharding_constraint: a spec naming a "
                    "Manual axis fails at LOWERING time inside a shard_map "
                    "region (the pp_engine failure class), and any spec "
                    "raises outside a mesh context; route through "
                    "areal_tpu.utils.jax_compat.with_sharding_constraint"
                ),
                key=make_key(
                    "MSH003",
                    sf.relpath,
                    sf.scope_of(call),
                    "with_sharding_constraint",
                ),
            )
