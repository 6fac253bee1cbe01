"""Repo-invariant linter: enforce by AST what the codebase keeps by
convention.

Rules (ids are the suppression keys):

* ``pallas-call`` — ``pl.pallas_call`` only inside ``kernels/``; every
  other layer talks to kernels through the ``kernels/*/ops.py`` wrappers
  via ``core/dispatch.py``.
* ``raw-digits`` — no arithmetic on raw ``RnsTensor.digits`` outside
  ``core/`` + ``kernels/``; digit planes are only combined by the
  residue primitives (layout moves like ``moveaxis``/``device_put`` are
  fine).
* ``backend-flag`` — backend selection goes through
  ``core/dispatch.resolve_backend``: no stray ``interpret=`` kwargs
  outside ``kernels/`` + ``core/dispatch.py`` and no ``use_pallas=``
  outside its legacy home ``core/rns_matmul.py``.
* ``host-in-jit`` — no ``time.*`` / ``np.random.*`` calls in the traced
  surface (``core/``, ``models/``, ``kernels/``): host calls burn in a
  constant at trace time and silently stop varying under jit.
* ``whole-array-vmem`` — every ``BlockSpec`` names an explicit block
  shape.  A shapeless/None BlockSpec maps the WHOLE operand into VMEM:
  fine for toy shapes, an unbounded-VMEM landmine at serving sizes (see
  analysis/kernel_audit.py for the budget it would blow).  A shapeless
  spec with a ``memory_space=`` other than VMEM (e.g. the moduli in SMEM)
  places the operand outside VMEM and passes.  Approved wrapper files
  are listed in ``_WHOLE_ARRAY_OK`` (currently none).

Suppression: ``# lint-ok: <rule>[, <rule>...] [reason]`` on the flagged
line or the line above; ``# lint-ok-file: <rule>`` anywhere in a file
suppresses the rule for the whole file (e.g. the autotuner, which times
on the host *by design*).

Run as a pytest (tests/test_analysis.py asserts zero unsuppressed
violations on ``src/``), as a CI job, or directly::

    PYTHONPATH=src python -m repro.analysis.lint
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
import sys

__all__ = ["LintViolation", "RULES", "lint_source", "run_lint", "main"]

RULES = {
    "pallas-call": "pl.pallas_call outside kernels/",
    "raw-digits": "arithmetic on raw RnsTensor.digits outside core/+kernels/",
    "backend-flag": "backend selection bypassing core/dispatch "
                    "(stray interpret=/use_pallas=)",
    "host-in-jit": "time.*/np.random.* call on a jitted code path",
    "whole-array-vmem": "BlockSpec without an explicit block shape "
                        "(whole-array VMEM residency)",
}

#: directories (relative to src/repro/) whose modules count as the traced
#: surface for host-in-jit
_TRACED_DIRS = ("core/", "models/", "kernels/")
#: where each bypass flag may legitimately appear
_INTERPRET_OK = ("kernels/", "core/dispatch.py")
_USE_PALLAS_OK = ("core/rns_matmul.py",)
#: wrapper files allowed to build whole-array VMEM BlockSpecs.  Empty on
#: purpose: every shipped kernel streams bounded blocks; add a file here
#: only with a VMEM argument in review.
_WHOLE_ARRAY_OK: tuple[str, ...] = ()
#: call names that count as arithmetic for raw-digits (layout moves and
#: placement don't — resident encode legitimately moveaxis/device_puts)
_ARITH_CALLS = {"matmul", "einsum", "dot", "tensordot", "remainder", "mod",
                "add", "subtract", "multiply", "sum", "prod", "cumsum"}

_SUPPRESS_RE = re.compile(r"#\s*lint-ok(?P<file>-file)?:\s*"
                          r"(?P<rules>[\w-]+(?:\s*,\s*[\w-]+)*)")


@dataclasses.dataclass(frozen=True)
class LintViolation:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _suppressions(src: str):
    """(file-wide rule set, line -> rule set).  A line-level pragma covers
    its own line and the one below it."""
    file_rules: set[str] = set()
    line_rules: dict[int, set[str]] = {}
    for i, text in enumerate(src.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = {r.strip() for r in m.group("rules").split(",")}
        if m.group("file"):
            file_rules |= rules
        else:
            line_rules.setdefault(i, set()).update(rules)
            line_rules.setdefault(i + 1, set()).update(rules)
    return file_rules, line_rules


def _is_digits_attr(node) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "digits"


class _Checker(ast.NodeVisitor):
    def __init__(self, rel: str):
        self.rel = rel
        self.found: list[tuple[int, str, str]] = []

    def flag(self, node, rule: str, message: str):
        self.found.append((node.lineno, rule, message))

    # --- pallas-call / backend-flag / host-in-jit (all Call-shaped) ------
    def visit_Call(self, node: ast.Call):
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if name == "pallas_call" and not self.rel.startswith("kernels/"):
            self.flag(node, "pallas-call",
                      "pallas_call belongs in kernels/ (route through "
                      "core/dispatch)")
        if name == "BlockSpec" and not (
                _WHOLE_ARRAY_OK and self.rel.startswith(_WHOLE_ARRAY_OK)):
            # an explicit block shape is any non-None first positional
            # arg or non-None block_shape= kwarg; bare/None BlockSpecs
            # map the whole operand into VMEM
            def _none(a):
                return isinstance(a, ast.Constant) and a.value is None
            shaped = bool(node.args) and not _none(node.args[0])
            shaped = shaped or any(
                kw.arg == "block_shape" and not _none(kw.value)
                for kw in node.keywords)
            # memory_space=pltpu.SMEM (or HBM/ANY) keeps it out of VMEM
            shaped = shaped or any(
                kw.arg == "memory_space"
                and isinstance(kw.value, ast.Attribute)
                and kw.value.attr != "VMEM"
                for kw in node.keywords)
            if not shaped:
                self.flag(node, "whole-array-vmem",
                          "BlockSpec without an explicit block shape pins "
                          "the whole operand in VMEM; pass a bounded "
                          "block (or list the file in _WHOLE_ARRAY_OK)")
        for kw in node.keywords:
            if kw.arg == "interpret" \
                    and not self.rel.startswith(_INTERPRET_OK):
                self.flag(node, "backend-flag",
                          "interpret= outside kernels//dispatch; use "
                          "dispatch.resolve_backend")
            if kw.arg == "use_pallas" \
                    and not self.rel.startswith(_USE_PALLAS_OK):
                self.flag(node, "backend-flag",
                          "use_pallas= is a legacy core/rns_matmul alias; "
                          "pass backend= instead")
        if self.rel.startswith(_TRACED_DIRS):
            if isinstance(fn, ast.Attribute):
                v = fn.value
                if isinstance(v, ast.Name) and v.id == "time":
                    self.flag(node, "host-in-jit",
                              f"time.{fn.attr} on a traced path")
                if isinstance(v, ast.Attribute) and v.attr == "random" \
                        and isinstance(v.value, ast.Name) \
                        and v.value.id in ("np", "numpy"):
                    self.flag(node, "host-in-jit",
                              f"np.random.{fn.attr} on a traced path")
        # raw-digits via arithmetic-shaped calls
        if name in _ARITH_CALLS and not self.rel.startswith(("core/",
                                                             "kernels/")):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if _is_digits_attr(arg):
                    self.flag(node, "raw-digits",
                              f".digits operand of {name}() outside core/")
        self.generic_visit(node)

    # --- raw-digits (operator-shaped) ------------------------------------
    def _digits_arith(self, node, operands):
        if self.rel.startswith(("core/", "kernels/")):
            return
        if any(_is_digits_attr(o) for o in operands):
            self.flag(node, "raw-digits",
                      "arithmetic on raw .digits outside core/ (use the "
                      "rt_*/dispatch primitives)")

    def visit_BinOp(self, node: ast.BinOp):
        self._digits_arith(node, (node.left, node.right))
        self.generic_visit(node)

    def visit_UnaryOp(self, node: ast.UnaryOp):
        if not isinstance(node.op, ast.Not):
            self._digits_arith(node, (node.operand,))
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._digits_arith(node, (node.target, node.value))
        self.generic_visit(node)


def lint_source(src: str, rel: str, path: str | None = None
                ) -> list[LintViolation]:
    """Lint one module's source.  ``rel`` is its path relative to
    ``src/repro/`` (rule scoping key); ``path`` is for messages."""
    file_rules, line_rules = _suppressions(src)
    checker = _Checker(rel)
    checker.visit(ast.parse(src))
    out = []
    for line, rule, message in checker.found:
        if rule in file_rules or rule in line_rules.get(line, ()):
            continue
        out.append(LintViolation(path or rel, line, rule, message))
    return sorted(out, key=lambda v: (v.path, v.line))


def run_lint(root=None) -> list[LintViolation]:
    """Lint every module under ``src/repro/`` plus the repo-root
    ``benchmarks/`` tree (zero violations is a CI gate; see
    .github/workflows/ci.yml job ``static-analysis``).  ``launch/`` lives
    under ``src/repro/`` and is covered by the main walk; benchmark
    modules get a ``benchmarks/`` rule-scoping prefix (outside
    ``kernels/``, so kernel calls and backend flags are flagged there
    like any other layer)."""
    if root is not None:
        bases = [(pathlib.Path(root), "")]
    else:
        base = pathlib.Path(__file__).resolve().parents[1]
        bases = [(base, ""), (base.parents[1] / "benchmarks", "benchmarks/")]
    out: list[LintViolation] = []
    for base, prefix in bases:
        if not base.is_dir():
            continue
        for py in sorted(base.rglob("*.py")):
            rel = prefix + py.relative_to(base).as_posix()
            out.extend(lint_source(py.read_text(), rel, str(py)))
    return out


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    violations = run_lint(args[0] if args else None)
    for v in violations:
        print(v)
    print(f"repro lint: {len(violations)} violation(s), "
          f"{len(RULES)} rules")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
