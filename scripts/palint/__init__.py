"""palint — the repo-native static-analysis engine (stdlib-only, jax-free).

Ten rounds of growth accumulated load-bearing conventions that nothing
machine-checked: standalone-loadable stdlib-only modules (the gate scripts
must run without jax), host-sync discipline in timed and
compiled hot paths (PR 3: "exposed transfer is booked as wait, never
compute"), jit cache-key stability, registry-backed vocabularies (metric
families, fault sites, span categories, env vars, the bench late-schema),
and a thread-heavy fleet/serving tier whose deadlock-freedom was proven
only by luck. This package is the ONE lint engine for all of them — the
reference has zero correctness tooling (SURVEY §4/§5.2: defensive
try/except and print-and-continue), so every pass here is a capability the
reference cannot express.

Engine contract:

- **passes** are sibling modules loaded by file path (no package-relative
  imports — the engine itself honors the standalone contract it enforces).
  Each exposes ``NAME``, ``DOC`` and ``run(ctx) -> list[dict]`` where a
  finding dict is ``{"path", "line", "code", "message"}``.
- **one Finding schema** (:class:`Finding`): pass name, repo-relative path,
  1-based line, a stable kebab-case code, and a human message. ``--check``
  exits nonzero iff any finding survives the pragmas.
- **pragmas** (per-line allowlist, justified in-line — the review speed
  bump the old test_telemetry allowlists created, now next to the code):

  - ``# palint: allow[<pass>] <justification>`` on the flagged line or the
    line above suppresses that pass's findings there. An EMPTY
    justification is itself a finding (``unjustified-pragma``), and a
    pragma that suppresses nothing is a finding (``stale-pragma``) — the
    staleness discipline the old allowlist test enforced centrally.
  - ``# guarded-by: <lock>`` / ``# unguarded: <reason>`` annotate shared
    attributes for the lock-discipline pass.
  - ``# palint: holds <lock>`` on a ``def`` line documents that the method
    is only called with ``<lock>`` already held.

- **JSON report** (``pa-palint/v1``) into ``ledger/palint.json``
  (``PA_LEDGER_DIR`` redirects, the perf-ledger rule).

The runtime companion is ``utils/lockcheck.py`` (PA_LOCKCHECK=1): the
static ``guarded-by`` annotations and the dynamic lock-acquisition-order
graph cross-check each other.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import sys
import tokenize

SCHEMA = "pa-palint/v1"
PKG_DIR = "comfyui_parallelanything_tpu"

# Pass modules, in report order. Loaded by file path from this directory —
# see _load_passes (no relative imports: the engine obeys the
# standalone-contract pass it ships).
PASS_FILES = (
    "standalone.py",
    "hostsync.py",
    "recompile.py",
    "registries.py",
    "lockorder.py",
    "observability.py",
)

# Applied to COMMENT tokens only (tokenize above), so no '#' anchor: the
# markers may trail an existing comment ("# socket map — guarded-by: _lock").
_ALLOW_RE = re.compile(
    r"palint:\s*allow\[([a-z0-9_,-]+)\]\s*(.*?)\s*$"
)
_GUARD_RE = re.compile(r"guarded-by:\s*([A-Za-z_][A-Za-z0-9_.]*)")
_UNGUARD_RE = re.compile(r"\bunguarded:\s*(\S.*)?$")
_HOLDS_RE = re.compile(r"palint:\s*holds\s+([A-Za-z_][A-Za-z0-9_.]*)")


class Finding:
    """The one finding schema every pass reports through."""

    __slots__ = ("pass_name", "path", "line", "code", "message")

    def __init__(self, pass_name: str, path: str, line: int, code: str,
                 message: str):
        self.pass_name = pass_name
        self.path = path
        self.line = int(line)
        self.code = code
        self.message = message

    def to_dict(self) -> dict:
        return {"pass": self.pass_name, "path": self.path, "line": self.line,
                "code": self.code, "message": self.message}

    def __str__(self) -> str:  # the human line: clickable path:line
        return (f"{self.path}:{self.line}: [{self.pass_name}/{self.code}] "
                f"{self.message}")


class Pragma:
    __slots__ = ("line", "passes", "reason", "used")

    def __init__(self, line: int, passes: tuple[str, ...], reason: str):
        self.line = line
        self.passes = passes
        self.reason = reason
        self.used = False


class SourceFile:
    """One parsed repo file: text, AST, comments, and palint pragmas."""

    def __init__(self, root: str, rel: str):
        self.root = root
        self.rel = rel
        self.path = os.path.join(root, rel)
        with open(self.path, encoding="utf-8") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.syntax_error: str | None = None
        try:
            self.tree: ast.Module | None = ast.parse(self.text)
        except SyntaxError as e:  # surfaced as a finding by lint()
            self.tree = None
            self.syntax_error = f"line {e.lineno}: {e.msg}"
        # line -> comment text (inline and full-line), via tokenize so
        # strings containing '#' can't fake a pragma.
        self.comments: dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.text).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError):
            pass
        self.pragmas: dict[int, Pragma] = {}
        self.guards: dict[int, str] = {}      # line -> lock name
        self.unguarded: dict[int, str] = {}   # line -> reason ("" = missing)
        self.holds: dict[int, str] = {}       # line -> lock name
        for line, text in self.comments.items():
            m = _ALLOW_RE.search(text)
            if m:
                passes = tuple(p.strip() for p in m.group(1).split(","))
                self.pragmas[line] = Pragma(line, passes, m.group(2).strip())
            m = _GUARD_RE.search(text)
            if m:
                self.guards[line] = m.group(1)
            m = _UNGUARD_RE.search(text)
            if m:
                self.unguarded[line] = (m.group(1) or "").strip()
            m = _HOLDS_RE.search(text)
            if m:
                self.holds[line] = m.group(1)

    def near(self, table: dict, line: int):
        """``table[line]`` (an annotation on the line itself), or the
        nearest entry in the contiguous comment block immediately above —
        the shared lookup rule for guards/unguarded/holds annotations."""
        if line in table:
            return table[line]
        ln = line - 1
        while ln > 0 and ln in self.comments and \
                self.lines[ln - 1].lstrip().startswith("#"):
            if ln in table:
                return table[ln]
            ln -= 1
        return None

    def allow_for(self, line: int, pass_name: str) -> Pragma | None:
        """The pragma covering ``line`` for ``pass_name``: on the line
        itself, or anywhere in the contiguous comment block immediately
        above it (multi-line justifications are encouraged)."""
        p = self.pragmas.get(line)
        if p is not None and pass_name in p.passes:
            return p
        ln = line - 1
        while ln > 0 and ln in self.comments and \
                self.lines[ln - 1].lstrip().startswith("#"):
            p = self.pragmas.get(ln)
            if p is not None and pass_name in p.passes:
                return p
            ln -= 1
        return None


class Ctx:
    """What a pass sees: the repo root and the parsed file set."""

    def __init__(self, root: str, files: list[SourceFile]):
        self.root = root
        self.files = files
        self._by_rel = {f.rel: f for f in files}

    def file(self, rel: str) -> SourceFile | None:
        return self._by_rel.get(rel)

    def package_files(self) -> list[SourceFile]:
        return [f for f in self.files if f.rel.startswith(PKG_DIR + "/")]


def collect_rels(root: str) -> list[str]:
    """The linted file set: the package, bench.py, and scripts/ (incl. this
    engine). tests/ and __graft_entry__.py are out of scope — fixtures and
    the driver harness would drown the signal."""
    rels: list[str] = []
    for base in (PKG_DIR, "scripts"):
        top = os.path.join(root, base)
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rels.append(os.path.relpath(
                        os.path.join(dirpath, fn), root))
    if os.path.exists(os.path.join(root, "bench.py")):
        rels.append("bench.py")
    return sorted(rels)


def _load_passes():
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    mods = []
    for fn in PASS_FILES:
        path = os.path.join(here, fn)
        spec = importlib.util.spec_from_file_location(
            f"pa_palint_{fn[:-3]}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mods.append(mod)
    return mods


def lint(root: str, rels: list[str] | None = None):
    """Run every pass over the repo at ``root``. Returns
    ``(findings, report_dict)`` with pragmas applied (suppressed findings
    dropped; stale/unjustified pragmas surfaced as findings)."""
    if rels is None:
        rels = collect_rels(root)
    files = [SourceFile(root, rel) for rel in rels]
    ctx = Ctx(root, files)
    findings: list[Finding] = []
    for f in files:
        if f.syntax_error:
            findings.append(Finding("engine", f.rel, 0, "syntax-error",
                                    f.syntax_error))
    counts: dict[str, int] = {}
    for mod in _load_passes():
        name = mod.NAME
        raw = mod.run(ctx)
        kept = 0
        for d in raw:
            sf = ctx.file(d["path"])
            pragma = sf.allow_for(d["line"], name) if sf else None
            if pragma is not None:
                pragma.used = True
                continue
            kept += 1
            findings.append(Finding(name, d["path"], d["line"], d["code"],
                                    d["message"]))
        counts[name] = kept
    # Pragma hygiene — the staleness check the old central allowlist test
    # did (`test_allowlist_entries_still_exist`), now per-pragma: one that
    # suppresses nothing must be removed with the site it covered, and one
    # without a justification is not an allowlist entry, it's a mute button.
    for f in files:
        for pragma in f.pragmas.values():
            if not pragma.reason:
                findings.append(Finding(
                    "engine", f.rel, pragma.line, "unjustified-pragma",
                    "palint allow pragma without an in-line justification"))
            elif not pragma.used:
                findings.append(Finding(
                    "engine", f.rel, pragma.line, "stale-pragma",
                    f"pragma allow[{','.join(pragma.passes)}] suppresses "
                    f"nothing — remove it with the site it covered"))
        # `# unguarded:` with no reason would silence the lock-discipline
        # inventory check unjustified — same mute-button rule as pragmas.
        for line, reason in sorted(f.unguarded.items()):
            if not reason:
                findings.append(Finding(
                    "engine", f.rel, line, "unjustified-annotation",
                    "`# unguarded:` without a reason — the form is "
                    "`# unguarded: <why this attr is deliberately lock-"
                    "free>`"))
    findings.sort(key=lambda x: (x.path, x.line, x.pass_name, x.code))
    # No timestamp: the report is committed (ledger/palint.json) and every
    # --check run rewrites it — deterministic bytes on an unchanged tree
    # keep the gate from churning the working copy.
    report = {
        "schema": SCHEMA,
        "root": os.path.abspath(root),
        "files_scanned": len(files),
        "counts": counts,
        "findings": [x.to_dict() for x in findings],
        "ok": not findings,
    }
    return findings, report


def report_path(root: str) -> str:
    led = os.environ.get("PA_LEDGER_DIR") or os.path.join(root, "ledger")
    return os.path.join(led, "palint.json")


def write_report(root: str, report: dict) -> str:
    path = report_path(root)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    return path


def env_table(root: str) -> str:
    """The generated ``PA_*`` env-var reference (markdown): the variable
    INVENTORY is the registry-consistency pass's own code scan (names
    cannot drift — the pass gates both directions), while the Purpose
    column is hand-written prose PRESERVED from the existing README table
    on regeneration; a variable the README has never described gets a TODO
    row naming its read sites. Regenerating is therefore always safe:
    ``python scripts/palint.py --env-table`` reproduces the committed
    table verbatim until the code's inventory changes."""
    rels = collect_rels(root)
    files = [SourceFile(root, rel) for rel in rels]
    ctx = Ctx(root, files)
    for mod in _load_passes():
        if mod.NAME == "registry-consistency":
            inv = mod.env_inventory(ctx)
            break
    else:  # pragma: no cover - PASS_FILES always includes registries
        raise RuntimeError("registry-consistency pass not found")
    purposes: dict[str, str] = {}
    try:
        with open(os.path.join(root, "README.md"), encoding="utf-8") as fh:
            for m in re.finditer(
                    r"^\|\s*`(PA_[A-Z0-9_]+)`\s*\|\s*(.*?)\s*\|\s*$",
                    fh.read(), re.MULTILINE):
                purposes[m.group(1)] = m.group(2)
    except OSError:
        pass
    lines = ["| Variable | Purpose |", "|---|---|"]
    for name in sorted(inv):
        purpose = purposes.get(name)
        if not purpose:
            where = sorted({rel.split("/")[-1] for rel in inv[name]})
            shown = ", ".join(where[:4]) + (", …" if len(where) > 4 else "")
            purpose = f"TODO: describe (read in {shown})"
        lines.append(f"| `{name}` | {purpose} |")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="palint.py",
        description="repo-native static analysis (see scripts/palint/)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if any finding survives the pragmas "
                         "(the ci_tier1.sh gate)")
    ap.add_argument("--json", action="store_true",
                    help="print the full JSON report instead of text")
    ap.add_argument("--env-table", action="store_true",
                    help="print the generated PA_* env-var markdown table "
                         "(the README reference is this output)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: the checkout containing this "
                         "script)")
    args = ap.parse_args(argv)
    root = args.root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if args.env_table:
        sys.stdout.write(env_table(root) + "\n")
        return 0
    findings, report = lint(root)
    path = write_report(root, report)
    if args.json:
        sys.stdout.write(json.dumps(report) + "\n")
    else:
        for f in findings:
            sys.stdout.write(str(f) + "\n")
        sys.stdout.write(
            f"palint: {len(findings)} finding(s) over "
            f"{report['files_scanned']} files — report {path}\n")
    if args.check and findings:
        return 1
    return 0
