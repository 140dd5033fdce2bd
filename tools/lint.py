"""Repo lint driver (``make lint``).

Runs the configured linters when they are installed, and a dependable
built-in floor everywhere else — the container CI image does not ship
ruff/mypy, and a lint target that silently no-ops teaches nothing:

1. **ruff** (``[tool.ruff]`` in pyproject.toml): lint + format check —
   used when importable/installed;
2. **mypy** (``[tool.mypy]``, permissive baseline) — used when
   installed;
3. **built-in fallback** (always available): per-file syntax check via
   ``compile()`` plus an AST pass for unused imports (ruff's F401) —
   the highest-signal subset of the configured ruleset, implemented
   against the same conventions (``# noqa`` respected, ``__init__.py``
   re-exports exempt, ``__all__`` counts as a use);
4. **vet rule-table drift check** (always available): every ``VET-*``
   id README.md cites must exist in ``analysis/findings.RULES`` and
   every registered rule must appear in README.md (range citations
   like ``VET-T001..T008`` expand) — the README tables are
   hand-maintained and this class of drift has already happened once
   (T010-T022/T026/M005-M006 shipped unregistered, breaking their
   suppression).  RULES is read by AST, not import, so the check
   never pays (or depends on) a jax import.

Exit status is nonzero on any finding, so the target composes into CI
recipes exactly like ``make resilience-smoke``.
"""
from __future__ import annotations

import ast
import pathlib
import re
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

#: directories scanned by the fallback linter (and passed to ruff)
TARGETS = ("isotope_tpu", "tests", "tools", "__graft_entry__.py")


def _files():
    for t in TARGETS:
        p = REPO / t
        if p.is_file():
            yield p
        else:
            yield from sorted(p.rglob("*.py"))


def _noqa_lines(src: str) -> set:
    return {
        i
        for i, line in enumerate(src.splitlines(), 1)
        if "# noqa" in line
    }


class _ImportUseScan(ast.NodeVisitor):
    """Collect module-level import bindings and every name usage."""

    def __init__(self) -> None:
        self.imports = {}  # name -> lineno (module level only)
        self.used = set()
        self._depth = 0

    def visit_Import(self, node: ast.Import) -> None:
        if self._depth == 0:
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                self.imports[name] = node.lineno

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return  # compiler directives, not bindings
        if self._depth == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                self.imports[a.asname or a.name] = node.lineno

    def _scope(self, node) -> None:
        self._depth += 1
        self.generic_visit(node)
        self._depth -= 1

    visit_FunctionDef = _scope
    visit_AsyncFunctionDef = _scope
    visit_ClassDef = _scope

    def visit_Name(self, node: ast.Name) -> None:
        self.used.add(node.id)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self.generic_visit(node)


def _string_uses(tree: ast.Module) -> set:
    """Names referenced via ``__all__`` or doctest-free string exports."""
    out = set()
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id == "__all__":
                for c in ast.walk(node):
                    if isinstance(c, ast.Constant) and isinstance(
                        c.value, str
                    ):
                        out.add(c.value)
    return out


def fallback_lint() -> int:
    """Syntax + unused-module-level-import check; returns #findings."""
    findings = 0
    for path in _files():
        rel = path.relative_to(REPO)
        try:
            src = path.read_text()
        except OSError as e:
            print(f"{rel}: unreadable: {e}")
            findings += 1
            continue
        try:
            tree = ast.parse(src, filename=str(rel))
        except SyntaxError as e:
            print(f"{rel}:{e.lineno}: syntax error: {e.msg}")
            findings += 1
            continue
        if path.name == "__init__.py":
            continue  # re-export modules import for the namespace
        scan = _ImportUseScan()
        scan.visit(tree)
        used = scan.used | _string_uses(tree)
        noqa = _noqa_lines(src)
        for name, lineno in sorted(
            scan.imports.items(), key=lambda kv: kv[1]
        ):
            if name in used or name == "_" or lineno in noqa:
                continue
            # conventional re-export / side-effect import aliases
            if name.startswith("_"):
                continue
            print(f"{rel}:{lineno}: F401 unused import: {name}")
            findings += 1
    return findings


#: a lone rule id, or a range over a shared letter (VET-T001..T008,
#: also tolerating a repeated letter on the right: VET-C001..C005)
_RULE_RE = re.compile(
    r"VET-([A-Z])(\d{3})(?:\.\.(?:[A-Z])?(\d{3}))?"
)


def registered_rules() -> set:
    """The rule ids in ``analysis/findings.RULES`` — by AST, so the
    drift check works without importing the package (or jax)."""
    src = (REPO / "isotope_tpu" / "analysis" / "findings.py").read_text()
    tree = ast.parse(src)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):  # RULES: Dict[...] = {..}
            targets = [node.target]
        else:
            continue
        for t in targets:
            if isinstance(t, ast.Name) and t.id == "RULES" and isinstance(
                node.value, ast.Dict
            ):
                return {
                    k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)
                }
    return set()


def readme_rules() -> set:
    """Every rule id README.md cites, with ranges expanded."""
    text = (REPO / "README.md").read_text()
    out = set()
    for m in _RULE_RE.finditer(text):
        letter, lo, hi = m.group(1), int(m.group(2)), m.group(3)
        hi = int(hi) if hi else lo
        for n in range(lo, hi + 1):
            out.add(f"VET-{letter}{n:03d}")
    return out


def rule_table_check() -> int:
    """README <-> findings.RULES drift; returns #findings."""
    registered = registered_rules()
    documented = readme_rules()
    findings = 0
    if not registered:
        print("tools/lint.py: could not parse RULES from "
              "isotope_tpu/analysis/findings.py")
        return 1
    for rule in sorted(documented - registered):
        print(f"README.md cites {rule} but analysis/findings.RULES "
              "does not register it (suppression of it would raise)")
        findings += 1
    for rule in sorted(registered - documented):
        print(f"analysis/findings.RULES registers {rule} but "
              "README.md never documents it (add it to a rule table, "
              "ranges like VET-T001..T008 count)")
        findings += 1
    return findings


def _run(cmd) -> int:
    print("+", " ".join(cmd))
    return subprocess.call(cmd, cwd=str(REPO))


def main() -> int:
    rc = 0
    ran_external = False
    if shutil.which("ruff"):
        ran_external = True
        rc |= _run(["ruff", "check", *TARGETS])
        rc |= _run(["ruff", "format", "--check", *TARGETS])
    if shutil.which("mypy"):
        ran_external = True
        rc |= _run(["mypy", "isotope_tpu"])
    n = fallback_lint() + rule_table_check()
    if n:
        print(f"lint: {n} finding(s)")
        rc |= 1
    if rc == 0:
        how = "ruff/mypy + builtin" if ran_external else (
            "builtin (ruff/mypy not installed)"
        )
        print(f"lint: clean ({how})")
    return rc


if __name__ == "__main__":
    sys.exit(main())
