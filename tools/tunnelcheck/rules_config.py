"""TC08: every ``EngineConfig`` field must be wired to a ``cli.py`` flag.

The config-rot counterpart to TC06 (ISSUE 5): EngineConfig grows a field
per feature, but a field nobody plumbs through the serve CLI is reachable
only by programmatic embedders — operators of the deployed binary simply
cannot turn it on, and nothing fails.  That is exactly how
``decode_steps_eager`` and ``prefill_rows`` sat env-only for four PRs
while README documented them as serving levers.

The rule fires on every dataclass field of a class named ``EngineConfig``
that never appears as a KEYWORD in an ``EngineConfig(...)`` construction
inside a ``cli.py`` — the one place the serve subcommand assembles the
engine's config from parsed flags.  Fields that are deliberately
env/programmatic-only (e.g. bucket geometry pinned by the compiled-program
set) carry a per-line waiver naming why, so the exemption is visible and
audited (``--show-waived``) instead of folklore.

Wiring surface resolution mirrors the registry rules: a scanned ``cli.py``
wins (fixture trees test against their own), else the repo's own
``p2p_llm_tunnel_tpu/cli.py`` is parsed — so scanning ``engine/engine.py``
alone still checks against the real CLI.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterator, List, Optional, Set, Tuple

from tools.tunnelcheck.core import (
    REPO_ROOT,
    ProjectContext,
    SourceFile,
    Violation,
    dotted_name,
)

CONFIG_CLASS = "EngineConfig"
CLI_REL = "p2p_llm_tunnel_tpu/cli.py"


def _config_fields(
    tree: ast.Module,
) -> Optional[List[Tuple[str, int, Optional[int]]]]:
    """``[(field, line, end_line)]`` of the dataclass ``EngineConfig``
    defined in ``tree``, or None when the module defines no such class."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef) and node.name == CONFIG_CLASS):
            continue
        fields = []
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ):
                fields.append(
                    (stmt.target.id, stmt.lineno, stmt.end_lineno)
                )
        return fields
    return None


def _wired_keywords(tree: ast.Module) -> Set[str]:
    """Keyword names of every ``EngineConfig(...)`` call in ``tree``."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = dotted_name(node.func)
        if dotted is None or dotted.split(".")[-1] != CONFIG_CLASS:
            continue
        out.update(kw.arg for kw in node.keywords if kw.arg is not None)
    return out


def _cli_keywords(ctx: ProjectContext) -> Optional[Set[str]]:
    """The wiring surface: union over scanned ``cli.py`` files, else the
    repo's own CLI module; None when neither exists (fixture-only runs
    with no CLI at all — nothing meaningful to check against)."""
    scanned = [sf for sf in ctx.files if sf.path.name == "cli.py"]
    if scanned:
        out: Set[str] = set()
        for sf in scanned:
            out |= _wired_keywords(sf.tree)
        return out
    candidate = REPO_ROOT / CLI_REL
    if candidate.is_file():
        try:
            return _wired_keywords(
                ast.parse(candidate.read_text(encoding="utf-8"))
            )
        except (OSError, SyntaxError):
            return None
    return None


def check_tc08(sf: SourceFile, ctx: ProjectContext) -> Iterator[Violation]:
    fields = _config_fields(sf.tree)
    if not fields:
        return iter(())
    wired = _cli_keywords(ctx)
    if wired is None:
        return iter(())
    out: List[Violation] = []
    for name, line, end_line in fields:
        if name in wired:
            continue
        out.append(
            Violation(
                "TC08",
                sf.path,
                line,
                f"EngineConfig.{name} is not wired to any cli.py flag "
                f"(no `{name}=` keyword in a cli.py EngineConfig(...) "
                "construction) — operators of the serve binary cannot "
                "reach it; add a flag or waive with the reason it is "
                "env/programmatic-only",
                end_line=end_line,
            )
        )
    return iter(out)
