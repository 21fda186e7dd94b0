#!/usr/bin/env python
"""Docs gate for CI (scripts/check.sh `docs` phase).

Two checks, either failing the build:

1. **Intra-repo markdown links.** Every relative link/image in the
   repo's documentation set (DESIGN.md, ROADMAP.md, CHANGES.md,
   README.md, docs/**.md) must resolve to a file that exists, and a
   ``#fragment`` must name a real heading anchor of the target file
   (GitHub's slug rules: lowercase, punctuation stripped, spaces to
   hyphens, ``-N`` suffixes for duplicates).  Fenced code blocks and
   inline code spans are ignored, so ``[G, C](...)``-shaped prose
   inside examples cannot false-positive.  External (http/mailto)
   links are not checked — CI must not depend on the network.

2. **Warning-free examples.** The runnable walkthroughs are executed
   with ``-W error::DeprecationWarning``: an example that drifts onto
   a deprecated entry point (the shims of DESIGN.md §13/§14) fails
   here before a user ever copies stale idiom.  Skipped with
   ``--no-examples`` (the link check is milliseconds; the examples
   are the slow half).

Exit code 0 = clean, 1 = findings (each printed as file:line).
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The documentation set: curated, not a blind walk — PAPER.md/PAPERS.md/
# SNIPPETS.md/ISSUE.md are generated research-context scratch whose
# external references are not this repo's contract.
DOC_FILES = ("README.md", "DESIGN.md", "ROADMAP.md", "CHANGES.md")
DOC_GLOBS = ("docs/*.md", "docs/**/*.md")

# Examples run by the gate: each must complete with DeprecationWarning
# promoted to an error, each as a fresh subprocess.
EXAMPLES = ("examples/quickstart.py", "examples/dm_elastic_cache.py")

_LINK_RE = re.compile(r"!?\[(?:[^\]]*)\]\(([^)\s]+)(?:\s+\"[^\"]*\")?\)")
_FENCE_RE = re.compile(r"^(```|~~~)")
_CODE_SPAN_RE = re.compile(r"`[^`]*`")
_HEADING_RE = re.compile(r"^(#{1,6})\s+(.*)$")


def _slugify(heading: str) -> str:
    """GitHub's anchor slug: strip inline-code backticks and markdown
    emphasis, lowercase, drop everything but word chars/spaces/hyphens,
    spaces become hyphens.  (`§2 Concurrency model, DM mapping` →
    `2-concurrency-model-dm-mapping`.)"""
    s = heading.strip().lower()
    s = s.replace("`", "").replace("*", "").replace("_", "")
    s = re.sub(r"[^\w\- ]", "", s)
    return s.replace(" ", "-")


def _strip_code(lines):
    """Yield (lineno, text) with fenced blocks blanked and inline code
    spans removed — links only count in prose."""
    fenced = False
    for i, ln in enumerate(lines, start=1):
        if _FENCE_RE.match(ln.strip()):
            fenced = not fenced
            yield i, ""
            continue
        yield i, "" if fenced else _CODE_SPAN_RE.sub("", ln)


def anchors_of(path: str) -> set:
    """All heading anchors of a markdown file, with GitHub's duplicate
    `-N` suffixing."""
    seen: dict = {}
    out = set()
    with open(path, encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    for _, ln in _strip_code(lines):
        m = _HEADING_RE.match(ln)
        if not m:
            continue
        slug = _slugify(m.group(2))
        n = seen.get(slug, 0)
        seen[slug] = n + 1
        out.add(slug if n == 0 else f"{slug}-{n}")
    return out


def check_links(doc_paths) -> list:
    findings = []
    anchor_cache: dict = {}
    for path in doc_paths:
        base = os.path.dirname(path)
        with open(path, encoding="utf-8") as fh:
            lines = fh.read().splitlines()
        rel = os.path.relpath(path, REPO_ROOT)
        for lineno, text in _strip_code(lines):
            for m in _LINK_RE.finditer(text):
                target = m.group(1)
                if re.match(r"^[a-zA-Z][a-zA-Z0-9+.-]*:", target):
                    continue  # http(s)/mailto/... — external, unchecked
                fpart, _, frag = target.partition("#")
                tpath = (os.path.normpath(os.path.join(base, fpart))
                         if fpart else path)
                if not os.path.exists(tpath):
                    findings.append(f"{rel}:{lineno}: broken link "
                                    f"{target!r} — no such file")
                    continue
                if frag:
                    if os.path.isdir(tpath) or not tpath.endswith(".md"):
                        continue  # anchors only checked into markdown
                    if tpath not in anchor_cache:
                        anchor_cache[tpath] = anchors_of(tpath)
                    if frag not in anchor_cache[tpath]:
                        findings.append(
                            f"{rel}:{lineno}: broken anchor {target!r} — "
                            f"no heading slugs to #{frag} in "
                            f"{os.path.relpath(tpath, REPO_ROOT)}")
    return findings


def check_examples() -> list:
    findings = []
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    # dm_elastic_cache shards over the devices it finds; a caller's
    # XLA_FLAGS would change that count, so the gate runs without it.
    env.pop("XLA_FLAGS", None)
    for ex in EXAMPLES:
        path = os.path.join(REPO_ROOT, ex)
        if not os.path.exists(path):
            findings.append(f"{ex}: gated example is missing")
            continue
        proc = subprocess.run(
            [sys.executable, "-W", "error::DeprecationWarning", path],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True,
            timeout=900)
        if proc.returncode != 0:
            tail = "\n".join((proc.stderr or proc.stdout)
                             .strip().splitlines()[-12:])
            findings.append(
                f"{ex}: exit {proc.returncode} under "
                f"-W error::DeprecationWarning\n    "
                + tail.replace("\n", "\n    "))
        else:
            print(f"  example OK: {ex}")
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-examples", action="store_true",
                    help="link/anchor check only (skip running examples)")
    args = ap.parse_args(argv)

    docs = [os.path.join(REPO_ROOT, f) for f in DOC_FILES
            if os.path.exists(os.path.join(REPO_ROOT, f))]
    for pat in DOC_GLOBS:
        docs.extend(sorted(glob.glob(os.path.join(REPO_ROOT, pat))))
    docs = list(dict.fromkeys(docs))
    print(f"check_docs: {len(docs)} markdown file(s)")
    findings = check_links(docs)
    if not args.no_examples:
        findings += check_examples()
    for f in findings:
        print(f"check_docs: FAIL {f}")
    if findings:
        print(f"check_docs: {len(findings)} finding(s)")
        return 1
    print("check_docs: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
