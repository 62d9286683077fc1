"""REP001 — all mesh/shard_map construction goes through ``repro.compat``.

Origin: PR 1 (platform policy, ROADMAP.md). ``jax.make_mesh`` grew
``axis_types``, ``shard_map`` moved out of ``jax.experimental`` and
renamed its replication-check kwarg, the ambient-mesh context moved from
``with mesh:`` to ``jax.sharding.use_mesh`` to ``jax.set_mesh`` — these
spellings drift across JAX releases, so they live in one place:
nothing outside ``src/repro/compat`` may touch them.
"""

from __future__ import annotations

import ast

from repro.analysis import lint

# dotted call/attribute chains that drift across JAX versions
_FORBIDDEN = {
    "jax.make_mesh": "jax.make_mesh",
    "jax.shard_map": "jax.shard_map",
    "jax.set_mesh": "jax.set_mesh",
    "jax.sharding.use_mesh": "jax.sharding.use_mesh",
    "jax.sharding.Mesh": "raw jax.sharding.Mesh construction",
    "jax.experimental.shard_map": "jax.experimental.shard_map",
    "jax.experimental.shard_map.shard_map": "jax.experimental.shard_map",
}

# import spellings of the same drift surface
_FORBIDDEN_IMPORT_FROM = {
    "jax": {"make_mesh", "shard_map", "set_mesh"},
    "jax.sharding": {"use_mesh", "Mesh"},
    "jax.experimental": {"shard_map"},
    "jax.experimental.shard_map": {"shard_map"},
}


def _applies(relpath: str) -> bool:
    return "repro/compat/" not in relpath


def _check(tree: ast.AST, relpath: str):
    from repro.analysis.rules import dotted

    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            name = dotted(node)
            if name in _FORBIDDEN:
                out.append((node.lineno, f"direct use of {_FORBIDDEN[name]}"))
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            banned = _FORBIDDEN_IMPORT_FROM.get(node.module or "", set())
            for alias in node.names:
                if alias.name in banned:
                    out.append((node.lineno,
                                f"direct import of {node.module}."
                                f"{alias.name}"))
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("jax.experimental.shard_map"):
                    out.append((node.lineno,
                                f"direct import of {alias.name}"))
    return out


RULE = lint.Rule(
    code="REP001",
    title="mesh/shard_map construction must go through repro.compat",
    origin="PR 1",
    fix_hint="use repro.compat.make_mesh / shard_map / use_mesh — the one "
             "home of the mesh spellings (ROADMAP platform policy)",
    applies=_applies,
    check=_check,
)
