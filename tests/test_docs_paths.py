"""The documents name only what the repository holds.

README.md, ARCHITECTURE.md and the verify skill send a reader to files
and to `make` targets.  A file that was deleted, or a lane that was
renamed, must not stay behind as an instruction: every repository path
and every `make` target they name has to exist."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "cranesched_tpu")
DOCS = ["README.md", "ARCHITECTURE.md", ".claude/skills/verify/SKILL.md"]

# what this repository's own files end in (upstream's .cpp/.h references
# are to PKUHPC/CraneSched, not to this tree)
SUFFIXES = (".py", ".sh", ".md", ".json", ".jsonl", ".yaml", ".ini",
            ".proto", ".cpp")
# made when the program runs, never committed
RUN_TIME = ("chiprun_out/", "bench_out/", "profiles/xla_cache",
            "profiles/capture-", "native/libcrane_native", "native/build")
TOKEN = re.compile(r"[A-Za-z0-9_.\-/]+")


def _missing_paths(text):
    """What in ``text`` reads as a path of this repository (a known
    top-level or package directory first, or a bare .py / .md name) and
    is not there."""
    # the tree git would commit: what .gitignore lists (scratch copies
    # of other commits among it) is not the repository
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as fh:
        ignored = {ln.strip().rstrip("/") for ln in fh} | {".git"}
    top = {e for e in os.listdir(REPO)
           if os.path.isdir(os.path.join(REPO, e))} - ignored
    sub = {e for e in os.listdir(PACKAGE)
           if os.path.isdir(os.path.join(PACKAGE, e))}
    basenames = set()
    for _root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in ignored]
        basenames.update(files)
    for raw in sorted(set(TOKEN.findall(text))):
        tok = re.sub(r":\d[\d,\-:]*$", "", raw.strip("."))  # file.py:12-34
        # a file by its suffix, or a directory by its trailing slash:
        # "tests/replays" in a sentence is two words, not a path
        if not (tok.endswith(SUFFIXES) or tok.endswith("/")):
            continue
        tok = tok.rstrip("/")
        if raw.startswith("/") or any(tok.startswith(p) for p in RUN_TIME):
            continue             # outside the repo, or made at run time
        first = tok.split("/", 1)[0]
        if "/" not in tok:
            # a bare name: only code and documents (a bare .yaml, .json
            # or .sh is a site's own file in an example), found by name
            there = not tok.endswith((".py", ".md")) or tok in basenames
        elif first in top:
            there = os.path.exists(os.path.join(REPO, tok))
        elif first in sub:
            there = os.path.exists(os.path.join(PACKAGE, tok))
        else:
            continue             # upstream's tree, a URL, a ratio
        if not there:
            yield tok


@pytest.mark.parametrize("doc", DOCS)
def test_every_named_path_and_make_target_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    # placeholders (<cell>, <yaml>, *.py, {a,b}) name no one file
    text = re.sub(r"\S*[<>*{}$]\S*", " ", text)
    missing = list(_missing_paths(text))

    with open(os.path.join(REPO, "Makefile"), encoding="utf-8") as fh:
        makefile = fh.read()
    with open(os.path.join(REPO, "pytest.ini"), encoding="utf-8") as fh:
        markers = set(re.findall(r"^\s+(\w+):", fh.read(), re.M))
    targets = set(re.findall(r"^([A-Za-z0-9_\-]+):", makefile, re.M))
    patterns = re.findall(r"^([A-Za-z0-9_\-]*)%:", makefile, re.M)
    for target in set(re.findall(r"\bmake ([a-z][a-z0-9_\-]+)", text)):
        # a pattern lane exists where its stem is a marker a test carries
        lanes = [target[len(p):] for p in patterns if target.startswith(p)]
        if target not in targets and not any(s in markers for s in lanes):
            missing.append(f"make {target}")
    assert not missing, f"{doc} names what does not exist: {sorted(missing)}"
    assert len(text) > 1000      # the document itself was found and read
