"""Doc CI gate: README.md / DESIGN.md must not reference things that no
longer exist.

  PYTHONPATH=src python -m benchmarks.check_docs [README.md DESIGN.md ...]

Three checks, all against the CURRENT tree (exit 1 on any failure):

- every ``--flag`` token the docs mention is defined by some
  ``add_argument`` in src/, benchmarks/, or examples/ (``--help`` is
  argparse-implicit);
- every ``SparsifierConfig.<field>`` attribute the docs mention is a
  real dataclass field;
- every backtick-quoted or markdown-linked file/dir path resolves
  (tried as-is and under src/ and src/repro/, with a trailing
  ``.member`` or ``::TestClass`` suffix stripped and ``{a,b}`` braces
  expanded).

Deliberately regex-simple: the point is that renaming a flag, config
field, or module without updating the docs fails CI — not perfect
markdown parsing.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_DOCS = ("README.md", "DESIGN.md", "EXPERIMENTS.md")
FLAG_RE = re.compile(r"(?<![\w-])(--[a-z][a-z0-9_-]+)")
ADD_ARG_RE = re.compile(r"add_argument\(\s*['\"](--[a-z0-9_-]+)['\"]")
SPARSIFIER_FIELD_RE = re.compile(r"SparsifierConfig\.([a-z_]+)")
BACKTICK_RE = re.compile(r"`([^`\n]+)`")
MDLINK_RE = re.compile(r"\]\(([^)#\s]+)\)")
IMPLICIT_FLAGS = {"--help"}
PATH_ROOTS = ("", "src/", "src/repro/")


def _all_basenames() -> set:
    """Every file basename in the tracked trees — the resolution rule
    for bare ``foo.py`` doc mentions (their directory is usually given
    by the surrounding prose/table cell)."""
    names = set()
    for sub in ("src", "benchmarks", "examples", "tests", ".github"):
        for _dirpath, _dirs, files in os.walk(os.path.join(ROOT, sub)):
            names.update(files)
    names.update(f for f in os.listdir(ROOT)
                 if os.path.isfile(os.path.join(ROOT, f)))
    return names


def _source_flags() -> set:
    flags = set(IMPLICIT_FLAGS)
    for f in os.listdir(ROOT):         # root entry points: chip_smoke.py
        if f.endswith(".py"):
            with open(os.path.join(ROOT, f)) as fh:
                flags.update(ADD_ARG_RE.findall(fh.read()))
    for sub in ("src", "benchmarks", "examples"):
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, sub)):
            for f in files:
                if not f.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, f)) as fh:
                    flags.update(ADD_ARG_RE.findall(fh.read()))
    return flags


def _expand_braces(token: str) -> list:
    m = re.search(r"\{([^{}]*)\}", token)
    if not m:
        return [token]
    out = []
    for part in m.group(1).split(","):
        out.extend(_expand_braces(token[:m.start()] + part + token[m.end():]))
    return out


def _path_candidates(token: str):
    token = token.split("::")[0].rstrip("/")
    for t in _expand_braces(token):
        # strip trailing ".member" accessor chains (core/aggregate.sync_
        # gradient -> core/aggregate), keeping real file extensions
        trims = [t]
        base = t
        for _ in range(3):
            stem, dot, ext = base.rpartition(".")
            if not dot or ext in ("py", "md", "json", "yml", "yaml", "txt"):
                break
            base = stem
            trims.append(base)
        for variant in trims:
            for root in PATH_ROOTS:
                yield os.path.join(ROOT, root, variant)
                if not variant.endswith((".py", ".md", ".json", ".yml")):
                    yield os.path.join(ROOT, root, variant + ".py")


def _looks_like_path(token: str) -> bool:
    if any(c in token for c in "()<>*=$ \t'\","):
        return False
    if token.startswith(("--", "http://", "https://")):
        return False
    return "/" in token or token.endswith((".py", ".md", ".json", ".yml"))


def check_doc(path: str, src_flags: set, fields: set,
              basenames: set) -> list:
    failures = []
    with open(path) as fh:
        text = fh.read()
    name = os.path.basename(path)
    for flag in sorted(set(FLAG_RE.findall(text))):
        if flag not in src_flags:
            failures.append(f"{name}: flag {flag} is not defined by any "
                            "add_argument in src/benchmarks/examples")
    for field in sorted(set(SPARSIFIER_FIELD_RE.findall(text))):
        if field not in fields:
            failures.append(f"{name}: SparsifierConfig.{field} is not a "
                            "config field")
    tokens = set(BACKTICK_RE.findall(text)) | set(MDLINK_RE.findall(text))
    for token in sorted(tokens):
        token = token.strip()
        if not _looks_like_path(token):
            continue
        if "/" not in token:
            if token not in basenames:
                failures.append(f"{name}: referenced file {token!r} does "
                                "not exist anywhere in the tree")
            continue
        if not any(os.path.exists(c) for c in
                   itertools.islice(_path_candidates(token), 64)):
            failures.append(f"{name}: referenced path {token!r} does not "
                            "resolve (tried as-is, under src/ and "
                            "src/repro/, and with trailing members "
                            "stripped)")
    return failures


def check(doc_paths) -> list:
    from repro.configs.base import SparsifierConfig
    fields = {f.name for f in dataclasses.fields(SparsifierConfig)}
    src_flags = _source_flags()
    basenames = _all_basenames()
    failures = []
    for p in doc_paths:
        full = p if os.path.isabs(p) else os.path.join(ROOT, p)
        if not os.path.exists(full):
            failures.append(f"doc file missing: {p}")
            continue
        failures.extend(check_doc(full, src_flags, fields, basenames))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("docs", nargs="*", default=list(DEFAULT_DOCS))
    args = ap.parse_args(argv)
    failures = check(args.docs or list(DEFAULT_DOCS))
    for f in failures:
        print(f"[check_docs] FAIL: {f}")
    if not failures:
        print(f"[check_docs] OK: {', '.join(args.docs or DEFAULT_DOCS)} "
              "reference only existing flags/fields/paths")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
