"""The documentation layer stays wired to the code: CLI reference /
parser flag parity, the doc-lint checks themselves, and the presence of
the README + DESIGN.md §14 the docs CI step gates on."""

import os
import re

from repro.launch import doclint
from repro.launch.dataplane import build_parser

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _live_flags() -> set:
    flags = {opt for a in build_parser()._actions
             for opt in a.option_strings if opt.startswith("--")}
    flags.discard("--help")
    return flags


def test_cli_reference_matches_live_parser():
    """Every parser flag is documented and every documented flag exists
    — docs/cli.md cannot rot in either direction."""
    text = open(os.path.join(ROOT, "docs", "cli.md")).read()
    documented = set(re.findall(r"`(--[\w-]+)[^`]*`", text))
    live = _live_flags()
    assert live - documented == set(), f"undocumented: {live - documented}"
    assert documented - live == set(), f"rotted: {documented - live}"


def test_new_slot_cache_flags_exist():
    assert {"--slot-cache", "--prefetch"} <= _live_flags()


def test_doclint_clean():
    """The full docs lint (dead paths, dead module refs, broken links
    and anchors, §N references, flag parity, API docstrings) passes on
    the committed tree — the same check CI runs."""
    assert doclint.run(ROOT) == []


def test_readme_covers_required_sections():
    text = open(os.path.join(ROOT, "README.md")).read()
    assert "## Quickstart" in text
    assert "## Architecture" in text
    assert "## Benchmarks" in text
    assert "examples/quickstart.py" in text
    assert "python -m pytest -x -q" in text         # tier-1 verify command
    baselines = [f for f in os.listdir(ROOT)
                 if re.fullmatch(r"BENCH_\d+\.json", f)]
    assert baselines
    for name in baselines:
        assert name in text                         # figure <-> baseline map


def test_design_has_section_14():
    text = open(os.path.join(ROOT, "DESIGN.md")).read()
    assert re.search(r"^## §14 ", text, re.M)
    for phrase in ("pointer flip", "shadow", "LRU", "prefetch"):
        assert phrase in text


def test_doclint_reads_whole_paths_only():
    """A ``tests/...`` tail inside a longer path is not a path of its
    own; a dead ``tests/...`` path still is."""
    problems = []
    doclint.check_paths(ROOT, "X.md", "see chipbench/tests/test_step_stall.py"
                        " and `chipbench/tests/test_program_spans.py`",
                        problems)
    assert problems == []
    doclint.check_paths(ROOT, "X.md", "see tests/test_no_such_file.py",
                        problems)
    assert problems == ["X.md: dead path 'tests/test_no_such_file.py'"]
