"""Documentation stays true or the build goes red.

Two enforcement layers for the docs/ overhaul (tier-1, no jax import):

* docs-freshness — every BENCH_fresh.json row name cited verbatim in
  EXPERIMENTS.md must exist in the committed BENCH_fresh.json, and
  docs/ARCHITECTURE.md + docs/SERVING.md must exist, be linked from the
  README, and reference real source files.  Perf claims that drift from
  the committed record fail here instead of silently rotting.
* pydocstyle-lite — an AST pass over the public surface (repro.api,
  repro.serve.engine, repro.core.builder): every public function/method
  carries a real docstring, and the lifecycle classes (FreshIndex,
  QueryEngine, IndexBuilder) additionally document every parameter by
  name and state a one-line `Concurrency:` contract on each non-property
  public method.
"""

import ast
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(rel: str) -> str:
    with open(os.path.join(ROOT, *rel.split("/"))) as f:
        return f.read()


# --------------------------------------------------------------------- #
# docs freshness
# --------------------------------------------------------------------- #
# a verbatim row citation: `fig3/...`, `fig5/...`, `serve/...`,
# `build/...`, `maint/...`, `quality/...`, `kernels/...` in backticks.
# Shorthand
# families (`build/pipeline/w{2,4}`, `fig3/query/*/ref`, `serve/...`)
# fall outside the character class or the filter below and are not
# checked — EXPERIMENTS.md must cite at least MIN_CITATIONS exact names
# so the check cannot go vacuous.
ROW_RE = re.compile(
    r"`((?:fig\d+|serve|build|maint|quality|kernels)/[A-Za-z0-9_/.-]+)`")
MIN_CITATIONS = 10


def _cited_rows(text: str):
    return [c for c in ROW_RE.findall(text)
            if ".." not in c and not c.endswith("/")]


def test_experiments_cites_only_committed_bench_rows():
    rows = {r["name"] for r in json.loads(_read("BENCH_fresh.json"))["rows"]}
    cited = _cited_rows(_read("EXPERIMENTS.md"))
    assert len(cited) >= MIN_CITATIONS, (
        f"EXPERIMENTS.md cites only {len(cited)} bench rows verbatim; "
        f"perf claims must reference committed BENCH_fresh.json row names")
    missing = sorted({c for c in cited if c not in rows})
    assert not missing, (
        f"EXPERIMENTS.md cites rows absent from the committed "
        f"BENCH_fresh.json: {missing}")
    quality = [c for c in cited if c.startswith("quality/")]
    assert quality, (
        "EXPERIMENTS.md §Approximate search must cite at least one "
        "committed `quality/...` bench row verbatim")
    kernels = [c for c in cited if c.startswith("kernels/")]
    assert kernels, (
        "EXPERIMENTS.md §Autotune must cite at least one committed "
        "`kernels/...` bench row verbatim")


def test_docs_exist_and_linked_from_readme():
    for rel in ("docs/ARCHITECTURE.md", "docs/SERVING.md"):
        assert os.path.exists(os.path.join(ROOT, *rel.split("/"))), rel
    readme = _read("README.md")
    assert "docs/ARCHITECTURE.md" in readme
    assert "docs/SERVING.md" in readme
    arch = _read("docs/ARCHITECTURE.md")
    for mod in ("core/refresh.py", "core/traverse.py", "core/builder.py",
                "core/index.py", "core/search.py", "serve/engine.py",
                "runtime/elastic.py"):
        assert mod in arch, f"ARCHITECTURE.md lost its map entry for {mod}"
    serving = _read("docs/SERVING.md")
    for knob in ("max_batch", "linger_ms", "workers", "donate",
                 "auto_compact_rows", "sync_every", "help_after_ms",
                 "latency_tiers", "recall_target",
                 "round_leaves", "dma_depth"):
        assert knob in serving, f"SERVING.md lost the {knob} knob"


def test_readme_migration_table_shows_no_deprecated_call_as_current():
    """The deprecated free functions may only appear in the 'old call'
    column / prose about deprecation — never as the recommended spelling
    (the stale-snippet bug this PR fixes)."""
    readme = _read("README.md")
    for line in readme.splitlines():
        if "|" not in line:
            continue
        cols = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cols) >= 2 and "make_sharded_search" in cols[-1]:
            raise AssertionError(
                f"deprecated make_sharded_search shown as the NEW call: "
                f"{line!r}")
        if len(cols) >= 2 and re.search(r"(?<![_.\w])search\(idx",
                                        cols[-1]):
            raise AssertionError(
                f"deprecated free search() shown as the NEW call: "
                f"{line!r}")


# --------------------------------------------------------------------- #
# pydocstyle-lite: the public surface documents itself
# --------------------------------------------------------------------- #
MODULES = {
    "src/repro/api.py": ("FreshIndex",),
    "src/repro/serve/engine.py": ("QueryEngine",),
    "src/repro/core/builder.py": ("IndexBuilder",),
}


def _is_property(node) -> bool:
    for d in node.decorator_list:
        if isinstance(d, ast.Name) and d.id == "property":
            return True
        if isinstance(d, ast.Attribute) and d.attr in ("setter", "getter"):
            return True
    return False


def _check_def(rel, cls, node, strict, problems):
    where = f"{rel}:{node.lineno} {(cls + '.') if cls else ''}{node.name}"
    doc = ast.get_docstring(node)
    if not doc or len(doc.strip()) < 20:
        problems.append(f"{where}: missing or trivial docstring")
        return
    if not strict:
        return
    if "Concurrency:" not in doc:
        problems.append(f"{where}: no 'Concurrency:' contract line")
    a = node.args
    params = [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)
              if p.arg not in ("self", "cls")]
    for name in params:
        if not re.search(rf"\b{re.escape(name)}\b", doc):
            problems.append(f"{where}: parameter '{name}' undocumented")


def test_public_surface_docstrings():
    problems = []
    for rel, contract_classes in MODULES.items():
        tree = ast.parse(_read(rel))
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not node.name.startswith("_"):
                    _check_def(rel, None, node, False, problems)
            elif isinstance(node, ast.ClassDef) \
                    and not node.name.startswith("_"):
                if not ast.get_docstring(node):
                    problems.append(f"{rel}: class {node.name} undocumented")
                strict_cls = node.name in contract_classes
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)) \
                            and not sub.name.startswith("_"):
                        _check_def(rel, node.name, sub,
                                   strict_cls and not _is_property(sub),
                                   problems)
    assert not problems, "public-surface docstring contract violated:\n" \
        + "\n".join(problems)
