"""Docs link check (stdlib only): every relative markdown link resolves.

Scans the repo's ``*.md`` files (top level + ``docs/``) for
``[text](target)`` links and inline-code references to repo paths, and
fails if a referenced file or directory does not exist.  External links
(``http``/``https``/``mailto``) are skipped — CI must not depend on
network reachability.  Run as ``python tools/check_docs.py`` from the repo
root.
"""
from __future__ import annotations

import os
import re
import sys

LINK = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(?:#[^)]*)?\)")
# `path/to/file.py` style inline-code refs that look like repo paths
CODE_PATH = re.compile(r"`((?:src|tests|examples|docs|tools|"
                       r"\.github)/[A-Za-z0-9_./\-]+)`")


def md_files(root: str) -> list[str]:
    out = [os.path.join(root, f) for f in sorted(os.listdir(root))
           if f.endswith(".md")]
    docs = os.path.join(root, "docs")
    if os.path.isdir(docs):
        out += [os.path.join(docs, f) for f in sorted(os.listdir(docs))
                if f.endswith(".md")]
    return out


def check(root: str = ".") -> list[str]:
    failures = []
    for path in md_files(root):
        base = os.path.dirname(path)
        with open(path) as f:
            text = f.read()
        refs = [(m, base) for m in LINK.findall(text)] + \
               [(m, root) for m in CODE_PATH.findall(text)]
        for target, anchor in refs:
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            resolved = os.path.normpath(os.path.join(anchor, target))
            if not os.path.exists(resolved):
                failures.append(f"{os.path.relpath(path, root)}: "
                                f"broken reference -> {target}")
    return failures


def main() -> None:
    failures = check(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
                     or ".")
    if failures:
        print("DOCS CHECK FAIL:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("docs check ok: all markdown references resolve")


if __name__ == "__main__":
    main()
