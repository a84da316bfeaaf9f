"""The documents a newcomer reads first describe the tree as it is: the
README's table of environment variables is the package's own list, and
every repository path that ``README.md`` and the verify skill name in
backticks exists."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV_NAME = re.compile(r"CHAINERMN_TPU_[A-Z0-9_]+")
PATH_ROOTS = ("chainermn_tpu/", "benchmarks/", "scripts/", "tests/",
              "examples/")


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


def test_readme_documents_exactly_the_environment_variables_the_package_reads():
    read = set()
    for root, _, files in os.walk(os.path.join(REPO, "chainermn_tpu")):
        for name in files:
            if name.endswith(".py"):
                read |= set(ENV_NAME.findall(_read(root, name)))
    readme = _read("README.md")
    section = readme.split("## Environment variables\n", 1)[1]
    section = section.split("\n## ", 1)[0]
    rows = [line.split("|") for line in section.splitlines()
            if line.startswith("| `CHAINERMN_TPU_")]
    documented = {row[1].strip().strip("`"): row[3].strip() for row in rows}
    assert set(documented) == read
    assert len(rows) == len(documented)          # one row a variable
    assert all(len(text) > 20 for text in documented.values()), documented
    # and nothing elsewhere in the README speaks of a variable not read
    assert set(ENV_NAME.findall(readme)) == read


@pytest.mark.parametrize("document", ["README.md",
                                      ".claude/skills/verify/SKILL.md"])
def test_paths_a_document_names_exist_and_the_old_benchmark_is_gone(
        document):
    text = _read(document)
    assert not re.search(r"\bbench\.py\b", text)
    named = [tok for tok in re.findall(r"`([^`\n]+)`", text)
             if tok.startswith(PATH_ROOTS)]
    assert named, "the document names no repository path at all"
    missing = sorted({
        tok for tok in named
        if not glob.glob(os.path.join(REPO, re.split(r"[ :(]", tok)[0]))})
    assert missing == []
