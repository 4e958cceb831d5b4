"""Rule framework: source loading, project context, waivers, the runner.

Everything here is stdlib-only on purpose (ISSUE 3): the checker must run
in any environment that can run the repo's tests — including ones without
jax, websockets, or cryptography installed — so rules work on the ``ast``
of the code, never by importing it.
"""

from __future__ import annotations

import ast
import hashlib
import io
import json
import re
import sys
import tokenize
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

#: Repo root, derived from this file's location (tools/tunnelcheck/core.py),
#: so registry files (protocol/frames.py, utils/metrics.py) resolve even when
#: the scan targets are test fixtures outside the tree.
REPO_ROOT = Path(__file__).resolve().parents[2]

# The id list stops at the first space so a waiver can carry a justification:
#   time.sleep(1)  # tunnelcheck: disable=TC01  startup-only, loop not running
_RULE_LIST = r"([A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)"
_WAIVER_RE = re.compile(r"#\s*tunnelcheck:\s*disable=" + _RULE_LIST)
_FILE_WAIVER_RE = re.compile(r"#\s*tunnelcheck:\s*disable-file=" + _RULE_LIST)


@dataclass
class Violation:
    rule: str
    path: Path
    line: int
    message: str
    #: Last line of the offending statement: a waiver comment anywhere on
    #: the statement (e.g. next to one argument of a multi-line call)
    #: suppresses, not just one on the anchor line.
    end_line: Optional[int] = None

    def render(self, root: Optional[Path] = None) -> str:
        p = self.path
        if root is not None:
            try:
                p = p.relative_to(root)
            except ValueError:
                pass
        return f"{p}:{self.line}: {self.rule} {self.message}"


@dataclass
class SourceFile:
    path: Path
    text: str
    tree: ast.Module
    lines: List[str]
    #: local name -> canonical dotted path ("jnp" -> "jax.numpy").
    aliases: Dict[str, str]
    #: line number -> set of waived rule ids ("all" waives everything).
    line_waivers: Dict[int, Set[str]] = field(default_factory=dict)
    file_waivers: Set[str] = field(default_factory=set)

    def waived(self, rule: str, line: int, end_line: Optional[int] = None) -> bool:
        if "all" in self.file_waivers or rule in self.file_waivers:
            return True
        for ln in range(line, (end_line or line) + 1):
            w = self.line_waivers.get(ln, ())
            if "all" in w or rule in w:
                return True
        return False


@dataclass
class FuncInfo:
    """Statically-extracted signature of one def/lambda."""

    name: str
    pos: List[str]  # positional-only + positional-or-keyword, in order
    n_pos_defaults: int
    kwonly: List[str]
    kwonly_required: List[str]
    has_vararg: bool
    has_kwarg: bool
    is_method: bool  # defined directly inside a class, not static/classmethod
    path: Path
    line: int

    @classmethod
    def from_node(
        cls,
        node: "ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda",
        path: Path,
        is_method: bool = False,
    ) -> "FuncInfo":
        a = node.args
        kw_required = [
            arg.arg
            for arg, d in zip(a.kwonlyargs, a.kw_defaults)
            if d is None
        ]
        return cls(
            name=getattr(node, "name", "<lambda>"),
            pos=[x.arg for x in a.posonlyargs + a.args],
            n_pos_defaults=len(a.defaults),
            kwonly=[x.arg for x in a.kwonlyargs],
            kwonly_required=kw_required,
            has_vararg=a.vararg is not None,
            has_kwarg=a.kwarg is not None,
            is_method=is_method,
            path=path,
            line=getattr(node, "lineno", 0),
        )

    def effective_pos(self, drop_self: bool) -> List[str]:
        return self.pos[1:] if (drop_self and self.is_method and self.pos) else self.pos


def _is_type_checking_test(test: ast.AST) -> bool:
    d = dotted_name(test)
    return d is not None and d.split(".")[-1] == "TYPE_CHECKING"


def iter_scope_statements(body: Iterable[ast.stmt]) -> Iterator[ast.AST]:
    """Statements executed AT RUNTIME in the scope owning ``body`` —
    descends into try/if/with/loop (and class) blocks but never into nested
    functions (bindings local to them) nor ``if TYPE_CHECKING:`` bodies
    (which never execute).  SOURCE ORDER is preserved so a rebound import
    name resolves to its last binding, like Python does."""
    for node in body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(node, ast.If) and _is_type_checking_test(node.test):
            yield from iter_scope_statements(node.orelse)
            continue
        yield node
        yield from iter_scope_statements(ast.iter_child_nodes(node))


def collect_import_aliases(
    nodes: Iterable[ast.AST], out: Optional[Dict[str, str]] = None
) -> Dict[str, str]:
    out = {} if out is None else out
    for node in nodes:
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    out[a.asname] = a.name
                else:
                    root = a.name.split(".")[0]
                    out[root] = root
        elif isinstance(node, ast.ImportFrom):
            if node.level or not node.module:
                continue  # relative imports stay project-local
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def module_aliases(tree: ast.Module) -> Dict[str, str]:
    """Map each MODULE-LEVEL import name to its canonical dotted origin.

    Function-local imports are deliberately excluded: a helper's
    ``from time import sleep`` must not make every other function's
    ``sleep`` resolve to ``time.sleep``.  Rules that care about local
    imports (TC01) overlay them per function scope.
    """
    return collect_import_aliases(iter_scope_statements(tree.body))


def dotted_name(node: ast.AST) -> Optional[str]:
    """"a.b.c" for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def resolve_dotted(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Canonical dotted path of an expression ("jnp.abs" -> "jax.numpy.abs")."""
    dotted = dotted_name(node)
    if dotted is None:
        return None
    head, _, rest = dotted.partition(".")
    origin = aliases.get(head)
    if origin is None:
        return dotted
    return f"{origin}.{rest}" if rest else origin


def _collect_waivers(text: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """Waivers from actual COMMENT tokens, never from string literals —
    a fixture string containing ``# tunnelcheck: disable-file=...`` must not
    waive anything in the file that carries it."""
    per_line: Dict[int, Set[str]] = {}
    whole_file: Set[str] = set()
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(text).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return per_line, whole_file
    for tok in tokens:
        if tok.type != tokenize.COMMENT or "tunnelcheck" not in tok.string:
            continue
        m = _WAIVER_RE.search(tok.string)
        if m:
            per_line.setdefault(tok.start[0], set()).update(
                r.strip() for r in m.group(1).split(",") if r.strip()
            )
        m = _FILE_WAIVER_RE.search(tok.string)
        if m:
            whole_file |= {r.strip() for r in m.group(1).split(",") if r.strip()}
    return per_line, whole_file


def load_source(path: Path) -> Tuple[Optional[SourceFile], Optional[Violation]]:
    try:
        text = path.read_text(encoding="utf-8")
        tree = ast.parse(text, filename=str(path))
    except (OSError, SyntaxError, ValueError) as e:
        line = getattr(e, "lineno", 1) or 1
        return None, Violation("TC00", path, line, f"unparseable: {e}")
    lines = text.splitlines()
    per_line, whole_file = _collect_waivers(text)
    return (
        SourceFile(
            path=path,
            text=text,
            tree=tree,
            lines=lines,
            aliases=module_aliases(tree),
            line_waivers=per_line,
            file_waivers=whole_file,
        ),
        None,
    )


def _parse_registry_file(rel: str, scanned: Sequence[SourceFile]) -> Optional[ast.Module]:
    """AST of a registry module: prefer a scanned copy, else the repo's own."""
    for sf in scanned:
        if sf.path.as_posix().endswith(rel):
            return sf.tree
    candidate = REPO_ROOT / rel
    if candidate.is_file():
        try:
            return ast.parse(candidate.read_text(encoding="utf-8"))
        except (OSError, SyntaxError):
            return None
    return None


def _enum_members(tree: ast.Module, class_name: str) -> List[str]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            out = []
            for stmt in node.body:
                if (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, int)
                ):
                    out.append(stmt.targets[0].id)
            return out
    return []


def _str_collection(tree: ast.Module, var_name: str) -> Set[str]:
    """String literals in a module-level ``NAME = {...}`` / frozenset / dict."""
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            target = node.target
            value = node.value
        else:
            continue
        if not (isinstance(target, ast.Name) and target.id == var_name):
            continue
        if isinstance(value, ast.Call):  # frozenset({...})
            if value.args:
                value = value.args[0]
        if isinstance(value, ast.Dict):
            return {
                k.value
                for k in value.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)
            }
        if isinstance(value, (ast.Set, ast.List, ast.Tuple)):
            return {
                e.value
                for e in value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            }
    return set()


class ProjectContext:
    """Cross-file knowledge shared by all rules for one run."""

    def __init__(self, files: Sequence[SourceFile]):
        self.files = list(files)
        self._callgraph = None
        self._attr_counts: Optional[Dict[str, int]] = None
        self._scoped_graphs: Dict[str, object] = {}
        self._interproc: Dict[str, object] = {}

        frames = _parse_registry_file(
            "p2p_llm_tunnel_tpu/protocol/frames.py", self.files
        )
        self.message_types: List[str] = (
            _enum_members(frames, "MessageType") if frames else []
        )
        self.error_codes: Set[str] = (
            _str_collection(frames, "ERROR_CODES") if frames else set()
        )
        metrics = _parse_registry_file(
            "p2p_llm_tunnel_tpu/utils/metrics.py", self.files
        )
        self.metrics_names: Set[str] = (
            _str_collection(metrics, "METRICS_CATALOG") if metrics else set()
        )
        tracing = _parse_registry_file(
            "p2p_llm_tunnel_tpu/utils/tracing.py", self.files
        )
        self.span_names: Set[str] = (
            _str_collection(tracing, "SPAN_CATALOG") if tracing else set()
        )
        flight = _parse_registry_file(
            "p2p_llm_tunnel_tpu/utils/flight.py", self.files
        )
        self.flight_fields: Set[str] = (
            _str_collection(flight, "FLIGHT_SCHEMA") if flight else set()
        )
        self.postmortem_fields: Set[str] = (
            _str_collection(flight, "POSTMORTEM_SCHEMA") if flight else set()
        )
        self.startup_fields: Set[str] = (
            _str_collection(flight, "STARTUP_SCHEMA") if flight else set()
        )

    @property
    def callgraph(self):
        """The project-wide call graph (tools.tunnelcheck.callgraph), built
        once per run on first use and shared by every rule — the cross-file
        resolution TC02 half-built, now a substrate layer."""
        if self._callgraph is None:
            from tools.tunnelcheck.callgraph import CallGraph

            self._callgraph = CallGraph(self.files)
        return self._callgraph

    def attr_function_count(self, attr: str) -> int:
        """In how many distinct functions (project-wide) is ``attr``
        accessed through any receiver?  TC13's shared-state gate."""
        if self._attr_counts is None:
            from tools.tunnelcheck.dataflow import attr_function_counts

            self._attr_counts = attr_function_counts(
                sf.tree for sf in self.files
            )
        return self._attr_counts.get(attr, 0)

    def scoped_callgraph(self, scope_part: str):
        """Call graph restricted to files whose path contains
        ``scope_part`` — the interprocedural rules analyze the package,
        not the tests/fixtures that happen to share a scan."""
        got = self._scoped_graphs.get(scope_part)
        if got is None:
            from tools.tunnelcheck.callgraph import CallGraph

            got = CallGraph([
                sf for sf in self.files
                if scope_part in sf.path.as_posix()
            ])
            self._scoped_graphs[scope_part] = got
        return got

    def interproc(self, key: str, build):
        """Memoized interprocedural fixpoint shared across the per-file
        rule passes of one run (and warmed before the fork in parallel
        runs, like :attr:`callgraph`)."""
        got = self._interproc.get(key)
        if got is None:
            got = build()
            self._interproc[key] = got
        return got



def iter_python_files(paths: Iterable[Path]) -> Iterator[Path]:
    seen: Set[Path] = set()

    def emit(f: Path) -> Iterator[Path]:
        r = f.resolve()
        if r not in seen:
            seen.add(r)
            yield f

    for p in paths:
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" not in f.parts:
                    yield from emit(f)
        elif p.suffix == ".py":
            yield from emit(p)


def all_rules() -> Dict[str, "object"]:
    """rule id -> check function ``(SourceFile, ProjectContext) -> Iterator``."""
    from tools.tunnelcheck import (
        rules_async,
        rules_atomicity,
        rules_config,
        rules_deps,
        rules_dispatch,
        rules_flight,
        rules_jax,
        rules_kvalign,
        rules_labels,
        rules_lifecycle,
        rules_metrics,
        rules_protocol,
        rules_queues,
        rules_retry,
        rules_taint,
        rules_tierpin,
        rules_tracing,
        rules_warmup,
    )

    return {
        "TC01": rules_async.check_tc01,
        "TC02": rules_jax.check_tc02,
        "TC03": rules_jax.check_tc03,
        "TC04": rules_deps.check_tc04,
        "TC05": rules_protocol.check_tc05,
        "TC06": rules_metrics.check_tc06,
        "TC07": rules_dispatch.check_tc07,
        "TC08": rules_config.check_tc08,
        "TC09": rules_tracing.check_tc09,
        "TC10": rules_queues.check_tc10,
        "TC11": rules_retry.check_tc11,
        "TC12": rules_labels.check_tc12,
        "TC13": rules_atomicity.check_tc13,
        "TC14": rules_taint.check_tc14,
        "TC15": rules_lifecycle.check_tc15,
        "TC16": rules_flight.check_tc16,
        "TC17": rules_warmup.check_tc17,
        "TC18": rules_tierpin.check_tc18,
        "TC19": rules_kvalign.check_tc19,
        "TC20": rules_tierpin.check_tc20,
        "TC21": rules_taint.check_tc21,
    }


RULE_SUMMARIES = {
    "TC00": "file fails to parse (always on)",
    "TC01": "blocking call (sleep/subprocess/socket/file IO) inside async def",
    "TC02": "jax.jit static/donate argnums+argnames or call arity vs wrapped signature",
    "TC03": "host sync (.item()/np.asarray/device_get/if-on-array) inside traced fns",
    "TC04": "module-level optional-dep import (websockets/cryptography) outside gated wrappers",
    "TC05": "non-exhaustive MessageType dispatch / typed_error code not in ERROR_CODES",
    "TC06": "metric name not declared in utils.metrics.METRICS_CATALOG",
    "TC07": "device dispatch inside a per-request/slot loop on the serving path",
    "TC08": "EngineConfig field not wired to a cli.py flag (config rot)",
    "TC09": "span name not in utils.tracing.SPAN_CATALOG / span emission inside traced fns",
    "TC10": "unbounded Queue/deque in endpoints/transport/protocol without a backpressure waiver",
    "TC11": "retry/backoff loop in cli.py/endpoints/transport without a cap+attempt bound or jitter",
    "TC12": "labeled Prometheus series interpolated outside the bounded registry helpers",
    "TC13": "read-modify-write of shared state straddles an await/yield without a lock",
    "TC14": "client-controlled header/body bytes reach a trusted sink unsanitized",
    "TC15": "span/slot/in-flight registration not released on every exit path (incl. generator aclose)",
    "TC16": "flight/postmortem/start-up field not in the flight.py registries / ops path matched outside http11.ops_route",
    "TC17": "dispatch-site program kind unreachable from the warmup/AOT plan generators (mid-serve cold-compile hole)",
    "TC18": "KV page bytes spliced into a device pool without the registered tier-boundary pin check (verify_page_pin)",
    "TC19": "packed-KV write outside the byte-aligned helpers (pack_int4 -> buffer write, or hand-rolled nibble merge)",
    "TC20": "extracted KV page bytes reach a tunnel send / tier write / splice without verify_page_pin on every path (interprocedural)",
    "TC21": "client-controlled header/body bytes laundered through helper functions reach a trusted sink (interprocedural TC14)",
}


# ---------------------------------------------------------------------------
# Per-file result cache (ISSUE 18)
# ---------------------------------------------------------------------------

#: Entries kept before the oldest are evicted — a soft cap so an abandoned
#: cache dir cannot grow without bound across branch switches.
_CACHE_MAX_ENTRIES = 4096


def _rules_digest() -> str:
    """Content hash of every module in tools/tunnelcheck plus the Python
    version: editing ANY rule or substrate file invalidates the whole
    cache, which is what keeps the self-run-clean gate honest."""
    h = hashlib.blake2b(digest_size=16)
    h.update(f"py{sys.version_info[0]}.{sys.version_info[1]}".encode())
    pkg = Path(__file__).resolve().parent
    for f in sorted(pkg.glob("*.py")):
        h.update(f.name.encode())
        try:
            h.update(f.read_bytes())
        except OSError:
            h.update(b"<unreadable>")
    return h.hexdigest()


def _file_sha(path: Path) -> Optional[str]:
    try:
        return hashlib.blake2b(path.read_bytes(), digest_size=16).hexdigest()
    except OSError:
        return None


def _cache_base(scan: Sequence[Tuple[Path, Optional[str]]],
                selected_key: str) -> str:
    """Digest of the ENTIRE scanned tree (paths + content hashes) plus the
    rule modules and selected-rule set.  Interprocedural rules make
    per-file isolation unsound — a helper edited in one file changes the
    findings in its callers — so a single changed file invalidates every
    entry.  The warm run this accelerates is the common one: nothing
    changed since the last ``make lint``."""
    h = hashlib.blake2b(digest_size=16)
    h.update(_rules_digest().encode())
    h.update(selected_key.encode())
    for path, sha in scan:
        h.update(path.as_posix().encode())
        h.update((sha or "<unreadable>").encode())
    return h.hexdigest()


def _cache_entry_path(cache_dir: Path, base: str, path: Path,
                      sha: Optional[str]) -> Path:
    h = hashlib.blake2b(digest_size=16)
    h.update(base.encode())
    h.update(path.as_posix().encode())
    h.update((sha or "<unreadable>").encode())
    return cache_dir / f"{h.hexdigest()}.json"


def _violations_to_wire(violations: Iterable[Violation]) -> List[List]:
    return [[v.rule, v.line, v.end_line, v.message] for v in violations]


def _violations_from_wire(rows: Iterable[List], path: Path) -> List[Violation]:
    return [Violation(r[0], path, r[1], r[3], end_line=r[2]) for r in rows]


def _cache_write(cache_dir: Path, base: str,
                 scan: Sequence[Tuple[Path, Optional[str]]],
                 active: Sequence[Violation],
                 waived: Sequence[Violation]) -> None:
    try:
        cache_dir.mkdir(parents=True, exist_ok=True)
    except OSError:
        return
    by_path: Dict[str, Tuple[List[Violation], List[Violation]]] = {}
    for v in active:
        by_path.setdefault(str(v.path), ([], []))[0].append(v)
    for v in waived:
        by_path.setdefault(str(v.path), ([], []))[1].append(v)
    for path, sha in scan:
        a, w = by_path.get(str(path), ([], []))
        entry = {
            "path": path.as_posix(),
            "active": _violations_to_wire(a),
            "waived": _violations_to_wire(w),
        }
        target = _cache_entry_path(cache_dir, base, path, sha)
        try:
            tmp = target.with_suffix(".tmp")
            tmp.write_text(json.dumps(entry), encoding="utf-8")
            tmp.replace(target)
        except OSError:
            return
    try:
        entries = sorted(cache_dir.glob("*.json"),
                         key=lambda p: p.stat().st_mtime)
        for stale in entries[:-_CACHE_MAX_ENTRIES]:
            stale.unlink(missing_ok=True)
    except OSError:
        pass


def _cache_try(cache_dir: Path, base: str,
               scan: Sequence[Tuple[Path, Optional[str]]]
               ) -> Optional[Tuple[List[Violation], List[Violation]]]:
    """All-or-nothing warm load: every scanned file must have an entry
    under the current tree digest, or the run falls back to a cold pass.
    A hit skips parsing entirely — the waiver partition was computed from
    identical bytes, so replaying it is sound."""
    active: List[Violation] = []
    waived: List[Violation] = []
    for path, sha in scan:
        entry_path = _cache_entry_path(cache_dir, base, path, sha)
        try:
            entry = json.loads(entry_path.read_text(encoding="utf-8"))
        except (OSError, ValueError):
            return None
        active.extend(_violations_from_wire(entry.get("active", []), path))
        waived.extend(_violations_from_wire(entry.get("waived", []), path))
    return active, waived


# ---------------------------------------------------------------------------
# Waiver audit (ISSUE 18)
# ---------------------------------------------------------------------------

def audit_waivers(
    files: Sequence[SourceFile],
    waived: Sequence[Violation],
    selected: Sequence[str],
    full_run: bool,
) -> List[Tuple[Path, int, str]]:
    """Stale ``# tunnelcheck: disable=`` comments: waivers whose rule no
    longer fires on the statement they annotate.

    No second no-waiver pass is needed — ``run_paths`` already computes
    every violation and only *partitions* on waivers, so the ``waived``
    list IS the set of suppressions that earned their keep.  A line waiver
    for rule R is live iff some waived R-violation's statement span covers
    its line; a file waiver iff some waived R-violation exists in the
    file.  ``all`` waivers are only judged on full runs (a subset run
    cannot tell whether an unselected rule justifies them), and rule ids
    that do not exist are always reported — a typo'd waiver suppresses
    nothing and reads as if it did.
    """
    known = set(RULE_SUMMARIES)
    judged = set(selected)
    covered: Dict[Tuple[str, str], List[Tuple[int, int]]] = {}
    for v in waived:
        covered.setdefault((str(v.path), v.rule), []).append(
            (v.line, v.end_line or v.line)
        )
    out: List[Tuple[Path, int, str]] = []
    for sf in files:
        key_path = str(sf.path)

        def live(rule: str, line: Optional[int]) -> bool:
            rules = [rule] if rule != "all" else sorted(
                {r for (p, r) in covered if p == key_path}
            )
            for r in rules:
                for lo, hi in covered.get((key_path, r), ()):
                    if line is None or lo <= line <= hi:
                        return True
            return False

        for line in sorted(sf.line_waivers):
            for rule in sorted(sf.line_waivers[line]):
                if rule != "all" and rule not in known:
                    out.append((sf.path, line,
                                f"waiver names unknown rule `{rule}`"))
                    continue
                if rule == "all" and not full_run:
                    continue
                if rule != "all" and rule not in judged:
                    continue
                if not live(rule, line):
                    out.append((
                        sf.path, line,
                        f"stale waiver: `{rule}` no longer fires on this "
                        "statement — delete the comment",
                    ))
        for rule in sorted(sf.file_waivers):
            if rule != "all" and rule not in known:
                out.append((sf.path, 1,
                            f"file waiver names unknown rule `{rule}`"))
                continue
            if rule == "all" and not full_run:
                continue
            if rule != "all" and rule not in judged:
                continue
            if not live(rule, None):
                out.append((
                    sf.path, 1,
                    f"stale file waiver: `{rule}` fires nowhere in this "
                    "file — delete the comment",
                ))
    return out


#: Fork-inherited state for parallel workers: set by :func:`run_paths`
#: immediately before the pool forks, so child processes see the parsed
#: files and warmed ProjectContext via copy-on-write instead of re-parsing
#: the tree per worker.
_FORK_STATE: Optional[Tuple[List[SourceFile], ProjectContext, List[str]]] = None


def _check_one(
    sf: SourceFile, ctx: ProjectContext, selected: Sequence[str],
    checks: Dict[str, object],
) -> Tuple[List[Violation], List[Violation]]:
    active: List[Violation] = []
    waived: List[Violation] = []
    for rule_id in selected:
        for v in checks[rule_id](sf, ctx):
            (waived if sf.waived(v.rule, v.line, v.end_line) else active).append(v)
    return active, waived


def _fork_worker(indices: Sequence[int]) -> Tuple[List[Violation], List[Violation]]:
    files, ctx, selected = _FORK_STATE  # type: ignore[misc]
    checks = all_rules()
    active: List[Violation] = []
    waived: List[Violation] = []
    for i in indices:
        a, w = _check_one(files[i], ctx, selected, checks)
        active.extend(a)
        waived.extend(w)
    return active, waived


def _selected_rules(
    checks: Dict[str, object], rules: Optional[Sequence[str]]
) -> List[str]:
    if rules is None:
        return list(checks)
    # TC00 (parse errors) is always on; anything else unknown is a
    # caller bug — silently running zero rules would read as "clean".
    unknown = set(rules) - set(checks) - {"TC00"}
    if unknown:
        raise ValueError(
            f"unknown rule id(s): {', '.join(sorted(unknown))}"
        )
    return [r for r in rules if r in checks]


def run_paths(
    paths: Sequence[Path],
    rules: Optional[Sequence[str]] = None,
    stats: Optional[Dict[str, int]] = None,
    jobs: int = 1,
    restrict: Optional[Set[Path]] = None,
    cache_dir: Optional[Path] = None,
    waiver_audit: Optional[List[Tuple[Path, int, str]]] = None,
) -> Tuple[List[Violation], List[Violation]]:
    """Run the suite. Returns (active_violations, waived_violations).

    ``stats``, when given, receives ``{"files": <count scanned>}`` so the
    CLI summary doesn't re-walk the tree (plus ``cache_hits``/
    ``cache_misses`` when ``cache_dir`` is set).

    ``jobs`` > 1 fans the per-file rule passes across a fork-based
    multiprocessing pool (135 files × 15 rules is embarrassingly parallel;
    cross-file context is parsed once in the parent and inherited
    copy-on-write).  Platforms without fork fall back to serial — results
    are byte-identical either way, including TC00 parse errors, which are
    collected in the parent so the exit-code and summary paths can never
    disagree about them.

    ``restrict`` limits which files get *findings* (the ``--changed-only``
    mode) while the whole path set still feeds cross-file context — a
    changed-file scan must see the unchanged registries and callees or
    TC02/TC06/TC07 would lose their cross-file resolution.

    ``cache_dir`` enables the per-file result cache.  Keys include every
    file's content hash, the rule-module digest, and the whole-tree digest
    — with interprocedural rules a single edited helper changes findings
    in its callers, so any change invalidates everything (the honest
    all-or-nothing trade, documented in README).  A full hit skips the
    check phase entirely.  ``restrict`` runs bypass the cache.

    ``waiver_audit``, when a list, is filled with :func:`audit_waivers`
    results for the checked files.
    """
    scan: List[Tuple[Path, Optional[str]]] = []
    for path in iter_python_files(paths):
        scan.append((path, None))
    if stats is not None:
        stats["files"] = len(scan)

    checks = all_rules()
    selected = _selected_rules(checks, rules)
    full_run = rules is None

    use_cache = cache_dir is not None and restrict is None
    base = ""
    if use_cache:
        scan = [(p, _file_sha(p)) for p, _ in scan]
        base = _cache_base(scan, ",".join(selected))
        cached = _cache_try(cache_dir, base, scan)
        if cached is not None:
            active, waived = cached
            if stats is not None:
                stats["cache_hits"] = len(scan)
                stats["cache_misses"] = 0
            if waiver_audit is not None:
                warm_files = []
                for p, _sha in scan:
                    sf, _err = load_source(p)
                    if sf is not None:
                        warm_files.append(sf)
                waiver_audit.extend(
                    audit_waivers(warm_files, waived, selected, full_run)
                )
            active.sort(key=lambda v: (str(v.path), v.line, v.rule))
            waived.sort(key=lambda v: (str(v.path), v.line, v.rule))
            return active, waived
        if stats is not None:
            stats["cache_hits"] = 0
            stats["cache_misses"] = len(scan)

    files: List[SourceFile] = []
    active = []
    waived = []
    for path, _sha in scan:
        sf, err = load_source(path)
        if err is not None:
            if restrict is None or path.resolve() in restrict:
                active.append(err)
        if sf is not None:
            files.append(sf)

    ctx = ProjectContext(files)

    if restrict is None:
        check_files = files
    else:
        check_files = [sf for sf in files if sf.path.resolve() in restrict]

    ran_parallel = False
    if jobs > 1 and len(check_files) > 1:
        import multiprocessing

        try:
            mp = multiprocessing.get_context("fork")
        except ValueError:
            mp = None
        if mp is not None:
            # Warm the lazily-built shared structures BEFORE forking, so
            # every worker inherits them instead of rebuilding per process.
            ctx.callgraph
            ctx.attr_function_count("")
            for rule_id in selected:
                warm = getattr(checks[rule_id], "warm", None)
                if warm is not None:
                    warm(ctx)
            global _FORK_STATE
            file_index = {id(sf): i for i, sf in enumerate(files)}
            chunks: List[List[int]] = [[] for _ in range(jobs)]
            for j, sf in enumerate(check_files):
                chunks[j % jobs].append(file_index[id(sf)])
            chunks = [c for c in chunks if c]
            _FORK_STATE = (files, ctx, list(selected))
            try:
                with mp.Pool(len(chunks)) as pool:
                    for a, w in pool.map(_fork_worker, chunks):
                        active.extend(a)
                        waived.extend(w)
                ran_parallel = True
            finally:
                _FORK_STATE = None
    if not ran_parallel:
        for sf in check_files:
            a, w = _check_one(sf, ctx, selected, checks)
            active.extend(a)
            waived.extend(w)

    if use_cache:
        _cache_write(cache_dir, base, scan, active, waived)
    if waiver_audit is not None:
        waiver_audit.extend(
            audit_waivers(check_files, waived, selected, full_run)
        )

    active.sort(key=lambda v: (str(v.path), v.line, v.rule))
    waived.sort(key=lambda v: (str(v.path), v.line, v.rule))
    return active, waived
