"""Docs cannot rot silently: every repo path referenced in `docs/` or the
README must exist, and every `path::name` anchor must point at a function,
class, or method that is still defined in that file."""
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

DOC_FILES = sorted(
    [os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
     if f.endswith(".md")] + ["README.md"])

# `src/...py::Name` or `src/...py::Class.method` inside backticks
ANCHOR_RE = re.compile(r"`([\w./-]+\.py)::([\w.]+)`")
# bare repo-relative paths inside backticks
PATH_RE = re.compile(
    r"`((?:src|tests|benchmarks|examples|docs)/[\w./-]+\.\w+|"
    r"(?:README|ROADMAP|PAPER|PAPERS|SNIPPETS|CHANGES)\.md)`")


def _read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def _anchors():
    out = []
    for doc in DOC_FILES:
        text = _read(doc)
        out += [(doc, path, name) for path, name in ANCHOR_RE.findall(text)]
    assert out, "no path::name anchors found — checker regex rotted?"
    return out


def _paths():
    out = []
    for doc in DOC_FILES:
        text = _read(doc)
        out += [(doc, p) for p in PATH_RE.findall(text)]
        out += [(doc, p) for p, _ in ANCHOR_RE.findall(text)]
    return out


@pytest.mark.parametrize("doc,path", sorted(set(_paths())))
def test_referenced_path_exists(doc, path):
    assert os.path.exists(os.path.join(ROOT, path)), \
        f"{doc} references missing path {path}"


@pytest.mark.parametrize("doc,path,name", sorted(set(_anchors())))
def test_anchor_resolves(doc, path, name):
    """The anchored name must be defined in the anchored file — `def name`
    / `class name` for top-level names, the method def for `Cls.method`."""
    full = os.path.join(ROOT, path)
    assert os.path.exists(full), f"{doc}: anchor file {path} missing"
    src = _read(path)
    leaf = name.split(".")[-1]
    pat = re.compile(rf"^\s*(?:def|class)\s+{re.escape(leaf)}\b|"
                     rf"^{re.escape(leaf)}\s*[:=]", re.MULTILINE)
    assert pat.search(src), \
        f"{doc}: anchor {path}::{name} does not resolve ({leaf} not " \
        f"defined in {path})"
    if "." in name:   # Cls.method: the class must exist too
        cls = name.split(".")[0]
        assert re.search(rf"^\s*class\s+{re.escape(cls)}\b", src,
                         re.MULTILINE), \
            f"{doc}: anchor class {cls} not defined in {path}"


def test_no_orphan_docs():
    """Every page in docs/ must be reachable from docs/index.md — a page
    nobody links is a page nobody reads, and it rots."""
    index = _read("docs/index.md")
    orphans = [os.path.basename(d) for d in DOC_FILES
               if d.startswith("docs/")
               and os.path.basename(d) != "index.md"
               and os.path.basename(d) not in index]
    assert not orphans, f"docs not linked from docs/index.md: {orphans}"


def test_cross_doc_links_resolve():
    """Every `docs/*.md` reference inside a doc page must point at a page
    that exists (stale cross-links are the docs equivalent of a dangling
    pointer)."""
    ref_re = re.compile(r"docs/[\w-]+\.md")
    stale = []
    for doc in DOC_FILES:
        for ref in set(ref_re.findall(_read(doc))):
            if not os.path.exists(os.path.join(ROOT, ref)):
                stale.append((doc, ref))
    assert not stale, f"stale cross-doc links: {stale}"


def test_equation_map_is_complete():
    """The docs system must keep covering the paper constructs the issue
    tracker promised: eq. 2, eq. 4, eq. 13, and Algorithm 1."""
    pages = {os.path.basename(d) for d in DOC_FILES}
    assert {"eq2_connectivity.md", "eq4_aggregation.md", "eq13_search.md",
            "algorithm1_transitions.md", "architecture.md",
            "index.md"} <= pages
    index = _read("docs/index.md")
    for page in ("eq2_connectivity", "eq4_aggregation", "eq13_search",
                 "algorithm1_transitions"):
        assert page in index, f"index.md no longer links {page}"
