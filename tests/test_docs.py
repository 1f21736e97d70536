"""The docs suite stays honest: README + docs/ links resolve, the pages
the README promises exist, and the link checker itself works."""

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))

from check_links import check_file, heading_slugs, markdown_files, slugify  # noqa: E402


def _doc_files():
    return markdown_files([str(ROOT / "README.md"), str(ROOT / "docs")])


def test_docs_suite_exists():
    names = {p.name for p in _doc_files()}
    assert {"README.md", "architecture.md", "backends.md",
            "benchmarks.md", "search.md"} <= names


def test_no_broken_links_or_anchors():
    errors = []
    for f in _doc_files():
        errors.extend(check_file(f))
    assert not errors, "\n".join(errors)


def test_slugify_matches_github_rules():
    assert slugify("Reading `BENCH_engine.json`") == \
        "reading-bench_enginejson"
    assert slugify("Escalation: the `auto` pipeline") == \
        "escalation-the-auto-pipeline"


def test_checker_catches_breakage(tmp_path):
    bad = tmp_path / "bad.md"
    bad.write_text("see [missing](nope.md) and [anchor](#nowhere)\n"
                   "# Real Heading\n")
    errors = check_file(bad)
    assert len(errors) == 2
    ok = tmp_path / "ok.md"
    ok.write_text("[self](#real-heading)\n# Real Heading\n")
    assert check_file(ok) == []
    assert "real-heading" in heading_slugs(ok)


def test_checker_ignores_code_and_handles_duplicate_headings(tmp_path):
    doc = tmp_path / "doc.md"
    doc.write_text("use `[text](not/a/link.md)` syntax\n"
                   "see [second](#example-1)\n"
                   "## Example\n## Example\n")
    assert check_file(doc) == []
    assert heading_slugs(doc) == {"example", "example-1"}


def test_tracing_section_names_every_span():
    """The operator docs' span table is ``repro.ged.spans.SPANS``: no span
    missing, none extra."""
    from repro.ged.spans import SPANS
    text = (ROOT / "docs" / "backends.md").read_text()
    section = text.split("\n## Tracing\n", 1)[1].split("\n## ", 1)[0]
    named = set(re.findall(r"^\| `(ged\.[a-z_.]+)` \|", section, re.M))
    assert named == {name for name, _ in SPANS}
