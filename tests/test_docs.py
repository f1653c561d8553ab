"""Every command a document tells the reader to run names something
that is in the tree: ``python <file>.py`` a file, ``python -m <module>``
a module (of this repository: a module whose top-level package is not
here, such as pytest, is someone else's).  Text only, nothing is run."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (["README.md", ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, REPO)
                 for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))
# Inside a fenced block or inline backticks: python[3] [-flags] target.
COMMAND = re.compile(
    r"python3?\s+(?:-[A-Za-z]\s+(?!-)\S+\s+)*?"
    r"(?:-m\s+(?P<module>[A-Za-z_][\w.]*)|(?P<file>[\w./-]+\.py)\b)")
# Where the documents run from: the checkout, or the container's
# WORKDIR (docs/deploy.md's Dockerfile.tpu).
RUN_FROM = ("", "examples")
# The reader's own script, by the names the documents give it.
PLACEHOLDERS = {"train.py", "your_driver.py", "my_train.py", "script.py"}


def _code(text):
    """The text of fenced blocks and inline backtick spans."""
    fenced = re.findall(r"```.*?```", text, flags=re.S)
    rest = re.sub(r"```.*?```", "", text, flags=re.S)
    return "\n".join(fenced + re.findall(r"`[^`\n]+`", rest))


def _module_exists(name):
    parts = name.split(".")
    if not os.path.exists(os.path.join(REPO, parts[0])) \
            and not os.path.exists(os.path.join(REPO, parts[0] + ".py")):
        return True     # not this repository's package
    base = os.path.join(REPO, *parts)
    return os.path.isfile(base + ".py") \
        or os.path.isfile(os.path.join(base, "__main__.py"))


@pytest.mark.parametrize("doc", DOCS)
def test_commands_name_files_and_modules_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        code = _code(f.read())
    missing = []
    for m in COMMAND.finditer(code):
        if m.group("module"):
            if not _module_exists(m.group("module")):
                missing.append("-m " + m.group("module"))
        elif os.path.basename(m.group("file")) not in PLACEHOLDERS \
                and not any(os.path.isfile(os.path.join(REPO, d,
                                                        m.group("file")))
                            for d in RUN_FROM):
            missing.append(m.group("file"))
    assert not missing, f"{doc} tells the reader to run {missing}"
