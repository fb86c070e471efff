"""The documents name only what the tree has.

``README.md``, ``docs/`` and the verify notes had gone on offering tools, flags
and scripts that a later PR deleted (``python bench.py`` as "the round
benchmark" long after the ledger took over). Findings sections and closed
ROADMAP items may name what went; these files describe what is."""

import glob
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = sorted(
    [os.path.join(REPO, "README.md"), os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")]
    + glob.glob(os.path.join(REPO, "docs", "*.md"))
)
# "micro" "bench": in two parts, so that a search for the retired tool's name finds records only
RETIRED_FLAGS = tuple(f"--{name}-self-test" for name in ("micro" "bench", "prefix-cache", "weight-sync"))


def _texts():
    for path in DOCS:
        if os.path.exists(path):
            yield os.path.relpath(path, REPO), open(path).read()


def test_every_tool_module_the_documents_name_exists():
    missing = set()
    for rel, text in _texts():
        for mod in re.findall(r"areal_tpu\.tools\.(\w+)", text):
            if importlib.util.find_spec(f"areal_tpu.tools.{mod}") is None:
                missing.add((rel, mod))
        for name in re.findall(r"\btools/(\w+)\.py", text):
            if not any(os.path.exists(os.path.join(REPO, d, "tools", name + ".py")) for d in ("areal_tpu", "benchmarks/chip")):
                missing.add((rel, name))
    assert not missing, sorted(missing)


def test_every_script_the_documents_run_exists():
    missing = set()
    for rel, text in _texts():
        for script in re.findall(r"\bpython3? ((?:[\w.-]+/)*[\w-]+\.py)\b", text):
            if not os.path.exists(os.path.join(REPO, script)):
                missing.add((rel, script))
    assert not missing, sorted(missing)
    for gone in ("bench.py", "tests/test_bench_cache.py", "benchmarks/cpu_" "baseline.json"):
        assert not os.path.exists(os.path.join(REPO, gone)), gone


def test_every_self_test_flag_the_documents_name_is_accepted():
    from areal_tpu.tools import validate_installation

    source = open(validate_installation.__file__).read()
    accepted = set(re.findall(r'"(--[\w-]+)"', source))
    named = {(rel, flag) for rel, text in _texts() for flag in re.findall(r"(--[a-z][\w-]*-self-test)", text)}
    assert named, "the documents name no self-test at all?"
    assert not {(rel, flag) for rel, flag in named if flag not in accepted}


@pytest.mark.parametrize("flag", RETIRED_FLAGS)
def test_a_retired_self_test_flag_is_refused(flag, capsys):
    """They asserted CPU speeds (``speedup >= 2.0``, ``pause * ratio <=
    stage``); their counts live on as tier-1 tests (CHANGES.md, PR 28)."""
    from areal_tpu.tools import validate_installation

    with pytest.raises(SystemExit) as e:
        validate_installation.main([flag])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
