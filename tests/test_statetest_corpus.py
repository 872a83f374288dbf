"""Conformance corpus (khipu_tpu/statetest.py over tests/fixtures/
state_tests/ — the ethereum/tests GeneralStateTest filler shape).

Every fixture file runs through the REAL execution stack (Ledger ->
EVM -> trie commit) and every case must land on the filler's post
state root exactly; tier-1 runs the corpus on every PR.
"""

import glob
import os

import pytest

from khipu_tpu.statetest import run_file

pytestmark = pytest.mark.conformance

CORPUS_DIR = os.path.join(
    os.path.dirname(__file__), "fixtures", "state_tests"
)
CORPUS = sorted(glob.glob(os.path.join(CORPUS_DIR, "*.json")))


def test_corpus_is_present():
    """The corpus shrinking silently would gate nothing — pin the
    floor (6 files as of PR 20; add, don't remove)."""
    assert len(CORPUS) >= 6, f"state test corpus missing: {CORPUS_DIR}"


@pytest.mark.parametrize(
    "path", CORPUS, ids=[os.path.basename(p) for p in CORPUS]
)
def test_statetest_file_passes(path):
    results = run_file(path)
    assert results, f"{path}: no runnable cases"
    failures = [
        f"{r.name}[{r.fork}#{r.index}]" for r in results if not r.ok
    ]
    assert not failures, f"{os.path.basename(path)}: {failures}"
