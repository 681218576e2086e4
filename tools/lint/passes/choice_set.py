"""choice-set (RL004): dispatch choice sets are constants synced to docs.

Mechanizes (and absorbs) ``tools/check_docs.py``: every public kwarg
validated by ``check_choice`` must

1. validate against a **module-level constant** (never an inline
   literal tuple -- those drift silently),
2. use a knob name registered in ``KNOBS`` below, and
3. have its registered constant match the ``docs/engines.md``
   choice-matrix row value-for-value and in order.

The constants are all literal string tuples, so the comparison is
fully static (AST-parsed; no jax import). ``tools/check_docs.py``
remains as a deprecation wrapper over the same comparison, keeping its
CLI contract (and ``tests/test_docs.py``) unchanged.

Adding a knob: define the tuple constant next to its engine, register
it in ``KNOBS``, and add the docs/engines.md row -- the pass fails
until all three agree, which is the point.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path

from tools.lint import astutil
from tools.lint.core import LintPass, Module, Project

# knob -> (repo-relative defining file, module-level constant name).
# The analogue of check_docs.code_choices(): new knobs register here.
KNOBS = {
    "engine": ("src/repro/core/__init__.py", "_CC_ENGINES"),
    "kernel_impl": ("src/repro/core/list_ranking.py", "KERNEL_IMPLS"),
    "hook_impl": ("src/repro/core/components.py", "HOOK_IMPLS"),
    "exchange": ("src/repro/distributed/graph.py", "EXCHANGES"),
    "rank_engine": ("src/repro/trees/compute.py", "RANK_ENGINES"),
    "pack_mode": ("src/repro/core/list_ranking.py", "PACK_MODES"),
    "kind": ("src/repro/serve/graph.py", "KINDS"),
    "sssp_engine": ("src/repro/core/sssp.py", "SSSP_ENGINES"),
    "pagerank_engine": ("src/repro/core/pagerank.py", "PAGERANK_ENGINES"),
    "on_overflow": ("src/repro/serve/engine.py", "OVERFLOW_POLICIES"),
    "on_failure": ("src/repro/serve/waves.py", "FAILURE_POLICIES"),
    "trace": ("src/repro/obs/trace.py", "TRACE_MODES"),
}

DOCS_REL = "docs/engines.md"

_ROW = re.compile(r"^\|\s*`(?P<knob>\w+)=`\s*\|(?P<values>[^|]*)\|")
_TOKEN = re.compile(r"`([^`]+)`")

_LITERAL_NODES = (ast.Tuple, ast.List, ast.Set)


def documented_choices_with_lines(text: str) -> dict:
    """{knob: (ordered value tuple, lineno)} from the choice-matrix
    table (the table after the ``<!-- choice-matrix`` marker; parsing
    stops at the next heading -- engines.md has other tables)."""
    out: dict = {}
    in_matrix = False
    for i, line in enumerate(text.splitlines(), start=1):
        if "<!-- choice-matrix" in line:
            in_matrix = True
            continue
        if in_matrix and line.startswith("#"):
            break
        if not in_matrix:
            continue
        m = _ROW.match(line.strip())
        if not m or m.group("knob") in out:
            continue
        values = tuple(_TOKEN.findall(m.group("values")))
        if values:
            out[m.group("knob")] = (values, i)
    return out


def documented_choices(text: str) -> dict:
    """{knob: ordered value tuple} -- the check_docs.py contract."""
    return {k: v for k, (v, _ln) in documented_choices_with_lines(text).items()}


def code_choices(root: str | Path) -> dict:
    """{knob: ordered value tuple} parsed statically from the KNOBS
    registry files. Raises if a registered constant is missing or not a
    literal string tuple (that IS drift)."""
    root = Path(root)
    trees: dict = {}
    out: dict = {}
    for knob, (rel, const) in KNOBS.items():
        if rel not in trees:
            trees[rel] = astutil.module_constants(
                ast.parse((root / rel).read_text(), filename=rel)
            )
        if const not in trees[rel]:
            raise LookupError(
                f"{knob}=: registered constant {const} not found as a "
                f"module-level literal string tuple in {rel}"
            )
        out[knob] = trees[rel][const][0]
    return out


def compare(doc: dict, code: dict) -> list:
    """[(knob, problem string)] -- the exact checks check_docs.py ran."""
    problems = []
    for knob, want in sorted(code.items()):
        got = doc.get(knob)
        if got is None:
            problems.append(
                (
                    knob,
                    f"{knob}=: no choice-matrix row in docs/engines.md "
                    f"(code has {want})",
                )
            )
        elif got != want:
            problems.append(
                (
                    knob,
                    f"{knob}=: docs/engines.md says {got}, code says {want}",
                )
            )
    for knob in sorted(set(doc) - set(code)):
        problems.append(
            (
                knob,
                f"{knob}=: documented in docs/engines.md but not in the "
                "choice-set registry -- add it to "
                "tools/lint/passes/choice_set.py KNOBS",
            )
        )
    return problems


class ChoiceSetPass(LintPass):
    name = "choice-set"
    code = "RL004"
    guideline = "C-docs"
    description = (
        "check_choice sites use registered module-level constants that "
        "match the docs/engines.md matrix"
    )

    def check_module(self, module: Module, project: Project):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            cn = astutil.call_name(node)
            if cn is None or cn.split(".")[-1] != "check_choice":
                continue
            if len(node.args) < 3:
                continue  # the definition / partial applications
            knob_arg, _value, choices = node.args[:3]
            if not (
                isinstance(knob_arg, ast.Constant)
                and isinstance(knob_arg.value, str)
            ):
                yield self.finding(
                    module,
                    node,
                    "check_choice knob name must be a string literal so "
                    "the choice-set pass can match it to docs/engines.md",
                )
                continue
            knob = knob_arg.value
            if isinstance(choices, _LITERAL_NODES) or (
                isinstance(choices, ast.Constant)
            ):
                yield self.finding(
                    module,
                    node,
                    f"check_choice('{knob}', ...) validates against an "
                    "inline literal; hoist it to a module-level constant "
                    "(inline sets drift out of sync with docs/engines.md)",
                )
            if knob not in KNOBS:
                yield self.finding(
                    module,
                    node,
                    f"check_choice knob '{knob}' is not registered; add "
                    "it to tools/lint/passes/choice_set.py KNOBS and give "
                    "it a docs/engines.md choice-matrix row",
                )

    def finalize(self, project: Project):
        docs_path = project.root / DOCS_REL
        if not docs_path.exists():
            yield self._docs_finding(
                1, f"{DOCS_REL} not found -- the choice matrix must exist"
            )
            return
        text = docs_path.read_text()
        doc_lines = documented_choices_with_lines(text)
        doc = {k: v for k, (v, _ln) in doc_lines.items()}
        try:
            code = code_choices(project.root)
        except (OSError, LookupError) as e:
            yield self._docs_finding(1, str(e))
            return
        for knob, problem in compare(doc, code):
            line = doc_lines.get(knob, ((), 1))[1]
            yield self._docs_finding(line, problem)

    def _docs_finding(self, line, message):
        from tools.lint.core import Finding

        return Finding(
            file=DOCS_REL,
            line=line,
            col=0,
            pass_name=self.name,
            code=self.code,
            message=message,
            guideline=self.guideline,
        )
