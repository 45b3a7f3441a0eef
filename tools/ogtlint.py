#!/usr/bin/env python
"""ogtlint: project-specific static analysis (stdlib `ast` only).

Every rule encodes an invariant that was at some point violated and
fixed by hand in this repo's history; the linter moves the enforcement
from reviewer memory into tier-1 (tests/test_ogtlint.py asserts zero
non-baselined findings over the tree), the way the PR 6 live-grep
catalog tests did — generalized into one analysis pass.

Rules:
  OGT010  every `OGT*`/`OGTPU*` env var READ in the code is documented
          in README.md (the knob-table invariant; a knob nobody can
          discover is a knob nobody tunes).
  OGT011  failpoint `_fp("site")` arming sites and diskfault
          `site="..."` consult labels agree BOTH WAYS with the torture
          catalogs (tools/torture.py KILL_SITES + DISKFAULT_SITES,
          tools/cluster_torture.py KILL_SITES).  Subsumes the three
          PR 6/PR 9 live-grep catalog tests, same failure messages.
  OGT020  server/http.py: every response outside `_send` itself (which
          drains globally) must justify its early-reply body-drain
          status — direct `send_response`/`send_error` calls are
          findings unless suppressed with a drain rationale (the PR 5/6
          keep-alive desync: unread POST bodies desync pipelined
          clients into BrokenPipe/BadStatusLine storms).
  OGT030  no bare `except:` anywhere; no `except Exception: pass`
          swallowing on write/durability paths (storage/, meta/,
          index/) — the PR 4 lost-batch hunt started from a swallowed
          error.
  OGT031  no raw `threading.Lock()`/`RLock()`/`Condition()`
          construction outside utils/lockdep.py — every product lock
          must be a lockdep-tracked class or the runtime validator is
          blind to it.
  OGT040  no `time.time()` for durations (GIL + NTP steps make it lie;
          `time.perf_counter()` is the duration clock).  Wall-clock
          timestamp uses carry a per-line suppression stating so.
  OGT050  stats/metric names fed to `GLOBAL.incr/set`, `histogram()`,
          `observe_ns()` match the PR 8 `ogt_<module>_<key>` grammar
          (`[a-z][a-z0-9_]*`): a dash or uppercase would be silently
          rewritten by the Prometheus sanitizer and split one logical
          family into two spellings.

Suppressions: append `# ogtlint: disable=OGT040` (comma-list ok) to the
finding's line — site-local, auditable in review.  Grandfathered
findings live in tools/ogtlint_baseline.json (committed; regenerate
with --fix-baseline): baselined findings don't fail the build but new
occurrences of the same (rule, file, detail) do.

Usage:
  python -m tools.ogtlint                     # lint the repo, text out
  python -m tools.ogtlint --format=github     # CI annotations
  python -m tools.ogtlint --fix-baseline      # rewrite the baseline
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_DEFAULT = os.path.join("tools", "ogtlint_baseline.json")

RULES = {
    "OGT010": "OGT*/OGTPU* env read not documented in README.md",
    "OGT011": "failpoint/diskfault site out of sync with torture catalog",
    "OGT020": "direct response write in server/http.py bypasses _send's body drain",
    "OGT030": "bare except / swallowed exception on a durability path",
    "OGT031": "raw threading lock construction outside utils/lockdep.py",
    "OGT040": "time.time() used where a duration clock belongs",
    "OGT050": "metric name outside the ogt_<module>_<key> grammar",
}

# write/durability paths for OGT030's swallow check (bare `except:` is
# flagged everywhere)
DURABILITY_PREFIXES = (
    os.path.join("opengemini_tpu", "storage") + os.sep,
    os.path.join("opengemini_tpu", "meta") + os.sep,
    os.path.join("opengemini_tpu", "index") + os.sep,
)

# OGT011 kill-rotation exemptions: armed failpoint sites that are NOT
# crash points on the single-node durability chain or the cluster
# decision edges, with the reason they can never fire in a torture child
# (kept verbatim from the PR 6/7/8/9 catalog tests this rule subsumes)
NOT_ON_CHAIN = {
    # object-store fault sites simulate REMOTE failures (torn/missing
    # bucket objects), not local crash points — the cold tier has its
    # own tests (test_objstore_remote) and the torture child runs no
    # object store, so a kill armed there would never fire
    "objstore-get-torn", "objstore-get-missing", "objstore-put-torn",
    # resource-governor decision edges (utils/governor.py): admission/
    # shed/backpressure control flow, not durability lock handoffs — the
    # torture child runs ungoverned (OGT_MEM_BUDGET_MB unset); their
    # schedule control is exercised by tests/test_governor.py instead
    "governor-admit", "governor-queue", "governor-shed",
    "governor-overdraft-kill", "governor-backpressure-on",
    "governor-backpressure-off",
    # materialized-rollup maintenance edges (storage/rollup.py): the
    # torture child declares no rollup specs; crash semantics are driven
    # deterministically by tests/test_rollup.py::TestCrashDurability
    "rollup-mark-dirty", "rollup-fold-before-write",
    "rollup-fold-after-write", "rollup-before-state-save",
    # observability span-ship edge (PR 8): a pure read-path site with no
    # durability state; covered by tests/test_observability.py
    "obs-before-span-ship",
    # media-fault quarantine edge (PR 9): a crash between detection and
    # the durable `.quar` marker re-detects on the next open
    # (idempotent); driven deterministically by tests/test_diskfault.py
    "quarantine-before-mark",
    # continuous-rule claim edge (promql/rules.py): the torture child
    # declares no rule groups; the mark-before-eval crash contract
    # (claimed tick re-evaluates once, no double-fire) is driven
    # deterministically by tests/test_rules.py
    "rules-mark-before-eval",
}

_METRIC_NAME = re.compile(r"^[a-z][a-z0-9_]*$")
_DISKFAULT_SITE = re.compile(r"^[a-z0-9-]+$")
_README_KNOB = re.compile(r"OGT(?:PU)?_[A-Z0-9_]+\*?")
_SUPPRESS = re.compile(r"#\s*ogtlint:\s*disable=([A-Z0-9,\s]+)")


class Finding:
    __slots__ = ("rule", "path", "line", "detail", "msg")

    def __init__(self, rule: str, path: str, line: int, detail: str,
                 msg: str):
        self.rule = rule
        self.path = path          # repo-relative, forward slashes
        self.line = line
        self.detail = detail      # stable identity token (baseline key)
        self.msg = msg

    def key(self) -> tuple:
        return (self.rule, self.path, self.detail)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.msg}"


def _rel(path: str, root: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


def _iter_py_files(root: str):
    """Product + tools — tests are consumers of these invariants, not
    subjects (they construct raw locks and fake knobs freely)."""
    roots = [os.path.join(root, "opengemini_tpu"),
             os.path.join(root, "tools")]
    for r in roots:
        for dirpath, dirs, files in os.walk(r):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def _suppressed(lines: list[str], lineno: int, rule: str) -> bool:
    if 1 <= lineno <= len(lines):
        m = _SUPPRESS.search(lines[lineno - 1])
        if m:
            rules = {r.strip() for r in m.group(1).split(",")}
            return rule in rules or "all" in rules
    return False


# -- per-file visitor ---------------------------------------------------------


class _FileFacts:
    """Cross-file facts one file contributes (OGT010/OGT011 inputs)."""

    def __init__(self):
        self.env_reads: list[tuple[str, int]] = []      # (name, line)
        self.fp_sites: list[tuple[str, int]] = []       # _fp("...")
        self.diskfault_sites: list[tuple[str, int]] = []  # site="..."


def _dotted(node) -> str:
    """'os.environ.get' for an Attribute chain, '' when not names."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _str_const(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class _Visitor(ast.NodeVisitor):
    def __init__(self, relpath: str, findings: list, facts: _FileFacts):
        self.relpath = relpath
        self.findings = findings
        self.facts = facts
        self.func_stack: list[str] = []
        # every alias this file binds the `time` MODULE to (import time,
        # import time as _t/_time, function-local variants) — OGT040
        # must see `_t.time()` or it silently exempts the alias idiom
        self.time_aliases: set[str] = set()
        # names bound to the time.time FUNCTION (`from time import time`)
        self.time_funcs: set[str] = set()
        self.is_http = relpath == "opengemini_tpu/server/http.py"
        self.is_lockdep = relpath == "opengemini_tpu/utils/lockdep.py"
        self.on_durability = relpath.replace("/", os.sep).startswith(
            DURABILITY_PREFIXES)

    def _add(self, rule, line, detail, msg):
        self.findings.append(Finding(rule, self.relpath, line, detail, msg))

    # -- import tracking (OGT040 alias resolution) --------------------
    def visit_Import(self, node):
        for alias in node.names:
            if alias.name == "time":
                self.time_aliases.add(alias.asname or "time")
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.module == "time":
            for alias in node.names:
                if alias.name == "time":
                    self.time_funcs.add(alias.asname or "time")
        self.generic_visit(node)

    # -- function context (OGT020 needs the enclosing method name) ----
    def visit_FunctionDef(self, node):
        self.func_stack.append(node.name)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- OGT030 -------------------------------------------------------
    def visit_ExceptHandler(self, node):
        if node.type is None:
            self._add(
                "OGT030", node.lineno, "bare-except",
                "bare `except:` swallows KeyboardInterrupt/SystemExit "
                "too — name the exceptions (or `except Exception` with "
                "a handler that records the error)")
        elif self.on_durability and self._is_broad(node.type) \
                and all(isinstance(s, (ast.Pass, ast.Continue))
                        for s in node.body):
            self._add(
                "OGT030", node.lineno, "swallow",
                "`except Exception: pass` on a write/durability path "
                "hides data loss (the PR 4 lost-batch class) — narrow "
                "the exception or record/annotate why it is safe")
        self.generic_visit(node)

    @staticmethod
    def _is_broad(type_node) -> bool:
        names = []
        if isinstance(type_node, ast.Tuple):
            names = [_dotted(e) for e in type_node.elts]
        else:
            names = [_dotted(type_node)]
        return any(n in ("Exception", "BaseException") for n in names)

    # -- calls: most rules key off Call nodes -------------------------
    def visit_Call(self, node):
        dotted = _dotted(node.func)

        # OGT031: raw lock construction
        if not self.is_lockdep and dotted in (
                "threading.Lock", "threading.RLock", "threading.Condition",
                "_threading.Lock", "_threading.RLock",
                "_threading.Condition"):
            kind = dotted.split(".", 1)[1]
            self._add(
                "OGT031", node.lineno, f"threading.{kind}",
                f"raw threading.{kind}() — use lockdep.{kind}() so the "
                "runtime lock-order validator sees it (utils/lockdep.py;"
                " pass-through alias when OGT_LOCKDEP is unset)")

        # OGT040: time.time() calls through ANY alias the file binds
        # the time module (or function) to
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "time"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in self.time_aliases) \
                or (isinstance(node.func, ast.Name)
                    and node.func.id in self.time_funcs):
            self._add(
                "OGT040", node.lineno, "time.time",
                "time.time() — use time.perf_counter() for durations; "
                "a deliberate wall-clock timestamp takes a per-line "
                "`# ogtlint: disable=OGT040` stating so")

        # OGT010: env reads — direct os.environ access AND the repo's
        # _env_int/_env_float-style wrapper helpers (utils/governor.py),
        # which take the knob name as a literal first argument; without
        # this a knob read through a helper would dodge the rule
        env_name = None
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else (node.func.id if isinstance(node.func, ast.Name) else "")
        if node.args and (
                dotted in ("os.environ.get", "_os.environ.get",
                           "os.getenv", "_os.getenv")
                or attr.lstrip("_") in ("env_int", "env_float", "env_str",
                                        "env_bool")):
            env_name = _str_const(node.args[0])
        if env_name and env_name.startswith("OGT"):
            self.facts.env_reads.append((env_name, node.lineno))

        # OGT011 facts: _fp("site") armings + diskfault site= labels
        if isinstance(node.func, ast.Name) and node.func.id == "_fp" \
                and node.args:
            site = _str_const(node.args[0])
            if site:
                self.facts.fp_sites.append((site, node.lineno))
        for kw in node.keywords:
            if kw.arg == "site":
                site = _str_const(kw.value)
                if site and _DISKFAULT_SITE.match(site):
                    self.facts.diskfault_sites.append((site, node.lineno))

        # OGT020: direct response writes in http.py
        if self.is_http and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("send_response", "send_error") \
                and _dotted(node.func.value) == "self" \
                and "_send" not in self.func_stack:
            meth = self.func_stack[-1] if self.func_stack else "<module>"
            self._add(
                "OGT020", node.lineno, meth,
                f"self.{node.func.attr}() outside _send skips the "
                "global early-reply body drain — an unread POST body "
                "desyncs keep-alive clients (BrokenPipe/BadStatusLine "
                "storms); route through _send/_send_json, or drain via "
                "_body() first and suppress with the rationale")

        # OGT050: metric-name grammar
        self._check_metric_name(node, dotted)

        self.generic_visit(node)

    def _check_metric_name(self, node, dotted: str):
        attr = node.func.attr if isinstance(node.func, ast.Attribute) \
            else (node.func.id if isinstance(node.func, ast.Name) else "")
        recv_ok = dotted.split(".")[0] in (
            "GLOBAL", "_STATS", "STATS", "stats", "_stats") \
            or dotted.endswith(".GLOBAL." + attr)
        if attr in ("incr", "set") and recv_ok and len(node.args) >= 2:
            parts = [_str_const(node.args[0]), _str_const(node.args[1])]
            if None in parts:
                return
            for p in parts:
                if not _METRIC_NAME.match(p):
                    self._add(
                        "OGT050", node.lineno, f"{parts[0]}.{parts[1]}",
                        f"stats name {parts[0]!r}/{parts[1]!r} exports "
                        f"as ogt_{parts[0]}_{parts[1]} — segments must "
                        "match [a-z][a-z0-9_]* or the Prometheus "
                        "sanitizer silently rewrites the family name")
                    return
        elif attr in ("histogram", "observe_ns") and node.args:
            name = _str_const(node.args[0])
            if name is not None and not _METRIC_NAME.match(name):
                self._add(
                    "OGT050", node.lineno, name,
                    f"histogram family {name!r} exports as ogt_{name} — "
                    "must match [a-z][a-z0-9_]*")

    # OGT010 also sees `os.environ["X"]`
    def visit_Subscript(self, node):
        if _dotted(node.value) in ("os.environ", "_os.environ"):
            name = _str_const(node.slice)
            if name and name.startswith("OGT"):
                self.facts.env_reads.append((name, node.lineno))
        self.generic_visit(node)


# -- cross-file rules ---------------------------------------------------------


def _documented_knobs(root: str) -> tuple[set, list]:
    """(exact names, wildcard prefixes) mentioned in README.md."""
    path = os.path.join(root, "README.md")
    exact, prefixes = set(), []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as fh:
            for tok in _README_KNOB.findall(fh.read()):
                if tok.endswith("*"):
                    prefixes.append(tok[:-1])
                else:
                    exact.add(tok)
    return exact, prefixes


def _catalog_literal(root: str, fname: str, varname: str):
    """AST-extract a list-of-strings literal from a tools/ harness
    WITHOUT importing it (torture.py imports the whole product)."""
    path = os.path.join(root, "tools", fname)
    if not os.path.exists(path):
        return [], 0
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == varname:
                    vals = [_str_const(e) for e in node.value.elts]
                    return [v for v in vals if v], node.lineno
    return [], 0


def _rule_ogt011(root: str, facts: dict) -> list[Finding]:
    """Bidirectional catalog sync — the PR 6/9 live-grep tests, as one
    lint rule (same failure messages, per-site findings)."""
    out = []
    kill, kill_ln = _catalog_literal(root, "torture.py", "KILL_SITES")
    ckill, ckill_ln = _catalog_literal(
        root, "cluster_torture.py", "KILL_SITES")
    dsites, d_ln = _catalog_literal(root, "torture.py", "DISKFAULT_SITES")
    catalog = set(kill) | set(ckill)
    armed, consulted = {}, {}
    for relpath, f in facts.items():
        if not relpath.startswith("opengemini_tpu/"):
            continue  # product sites only: harness/test arms are not
        for site, ln in f.fp_sites:      # durability-chain coverage
            armed.setdefault(site, (relpath, ln))
        for site, ln in f.diskfault_sites:
            consulted.setdefault(site, (relpath, ln))
    if not catalog and not dsites:
        return out  # fixture tree without harness catalogs: rule is moot
    for site in sorted(catalog - set(armed)):
        path = "tools/cluster_torture.py" if site in ckill \
            else "tools/torture.py"
        ln = ckill_ln if site in ckill else kill_ln
        out.append(Finding(
            "OGT011", path, ln, site,
            f"torture sites not armed anywhere: {{{site!r}}} — the "
            "catalog entry no longer matches an `_fp(...)` site, so it "
            "silently stopped being tortured"))
    for site in sorted(set(armed) - catalog - NOT_ON_CHAIN):
        relpath, ln = armed[site]
        out.append(Finding(
            "OGT011", relpath, ln, site,
            f"armed sites missing from the torture kill rotation: "
            f"{{{site!r}}} — add it to tools/torture.py KILL_SITES / "
            "tools/cluster_torture.py KILL_SITES (and the README "
            "catalog), or to ogtlint.NOT_ON_CHAIN with the reason it "
            "cannot fire in a torture child"))
    dset = set(dsites)
    for site in sorted(dset - set(consulted)):
        out.append(Finding(
            "OGT011", "tools/torture.py", d_ln, site,
            f"diskfault site catalog out of sync: missing from code "
            f"{{{site!r}}}"))
    for site in sorted(set(consulted) - dset):
        relpath, ln = consulted[site]
        out.append(Finding(
            "OGT011", relpath, ln, site,
            f"diskfault site catalog out of sync: missing from catalog "
            f"{{{site!r}}} — every storage IO chokepoint consult label "
            "belongs in tools/torture.py DISKFAULT_SITES"))
    return out


def _rule_ogt010(root: str, facts: dict) -> list[Finding]:
    exact, prefixes = _documented_knobs(root)
    out = []
    for relpath, f in sorted(facts.items()):
        for name, ln in f.env_reads:
            if name in exact or any(name.startswith(p) for p in prefixes):
                continue
            out.append(Finding(
                "OGT010", relpath, ln, name,
                f"env knob {name} is read here but missing from the "
                "README knob documentation — every OGT*/OGTPU* knob "
                "must be discoverable"))
    return out


# -- driver -------------------------------------------------------------------


def collect_findings(root: str) -> list[Finding]:
    findings: list[Finding] = []
    facts: dict[str, _FileFacts] = {}
    for path in _iter_py_files(root):
        relpath = _rel(path, root)
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError as e:
            findings.append(Finding(
                "SYNTAX", relpath, e.lineno or 1, "syntax",
                f"does not parse: {e.msg}"))
            continue
        f = _FileFacts()
        facts[relpath] = f
        file_findings: list[Finding] = []
        _Visitor(relpath, file_findings, f).visit(tree)
        lines = src.split("\n")
        findings.extend(
            fi for fi in file_findings
            if not _suppressed(lines, fi.line, fi.rule))
        # suppressions apply to the cross-file rules' fact sites too
        f.env_reads = [(n, ln) for n, ln in f.env_reads
                       if not _suppressed(lines, ln, "OGT010")]
        f.fp_sites = [(n, ln) for n, ln in f.fp_sites
                      if not _suppressed(lines, ln, "OGT011")]
        f.diskfault_sites = [(n, ln) for n, ln in f.diskfault_sites
                             if not _suppressed(lines, ln, "OGT011")]
    findings.extend(_rule_ogt010(root, facts))
    findings.extend(_rule_ogt011(root, facts))
    findings.sort(key=lambda fi: (fi.path, fi.line, fi.rule))
    return findings


def load_baseline(path: str) -> dict:
    """(rule, path, detail) -> grandfathered occurrence count."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    out: dict[tuple, int] = {}
    for e in doc.get("entries", []):
        key = (e["rule"], e["path"], e["detail"])
        out[key] = out.get(key, 0) + int(e.get("count", 1))
    return out


def apply_baseline(findings: list[Finding], baseline: dict
                   ) -> list[Finding]:
    """Findings beyond their baselined count (new code must be clean;
    grandfathered sites stay visible in the committed baseline, never
    silently ignored)."""
    budget = dict(baseline)
    fresh = []
    for f in findings:
        k = f.key()
        if budget.get(k, 0) > 0:
            budget[k] -= 1
        else:
            fresh.append(f)
    return fresh


def write_baseline(path: str, findings: list[Finding]) -> None:
    counts: dict[tuple, int] = {}
    for f in findings:
        counts[f.key()] = counts.get(f.key(), 0) + 1
    entries = [
        {"rule": r, "path": p, "detail": d, "count": c}
        for (r, p, d), c in sorted(counts.items())
    ]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"comment": (
            "ogtlint grandfathered findings. Every entry is a known, "
            "visible debt item: new occurrences beyond `count` fail "
            "tier-1 (tests/test_ogtlint.py). Regenerate with "
            "`python -m tools.ogtlint --fix-baseline` only after "
            "reviewing WHY each new finding should be grandfathered "
            "instead of fixed."), "entries": entries}, fh, indent=1)
        fh.write("\n")


def render(findings: list[Finding], fmt: str) -> str:
    if fmt == "github":
        # GitHub Actions workflow-command annotations
        return "\n".join(
            f"::error file={f.path},line={f.line},"
            f"title=ogtlint {f.rule}::{f.msg}" for f in findings)
    if fmt == "json":
        return json.dumps([
            {"rule": f.rule, "path": f.path, "line": f.line,
             "detail": f.detail, "msg": f.msg} for f in findings],
            indent=1)
    return "\n".join(f.render() for f in findings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ogtlint", description=__doc__.split("\n", 1)[0])
    ap.add_argument("--root", default=REPO_ROOT)
    ap.add_argument("--format", choices=("text", "github", "json"),
                    default="text")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default {BASELINE_DEFAULT} "
                         "under --root)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, baselined or not")
    ap.add_argument("--fix-baseline", action="store_true",
                    help="rewrite the baseline from current findings")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root)
    bl_path = args.baseline or os.path.join(root, BASELINE_DEFAULT)
    findings = collect_findings(root)
    if args.fix_baseline:
        write_baseline(bl_path, findings)
        print(f"baseline: {len(findings)} finding(s) -> {bl_path}")
        return 0
    if not args.no_baseline:
        findings = apply_baseline(findings, load_baseline(bl_path))
    out = render(findings, args.format)
    if out:
        print(out)
    if findings:
        print(f"\nogtlint: {len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
