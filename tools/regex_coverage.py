"""Measure device regex coverage over the reference's test corpus.

Extracts candidate patterns from the reference's regex suites
(`tests/.../RegularExpressionTranspilerSuite.scala` + Parser/Regression
suites — the same corpus the reference validates its own transpiler on),
keeps the ones that are valid Java-style regexes (proxy:
Python `re` compiles them), and reports what fraction this engine's DFA
accepts on-device, by mode:

  rlike   — membership only (search_prefix=True)
  extent  — span-consuming callers (replace/extract/split) that also
            need Java/POSIX extent agreement (extent_exact=True)

Rejection reasons are bucketed so the top lift targets are visible.
Writes docs/regex_coverage.md.

Run from the repo root:  python tools/regex_coverage.py [ref_root]
"""

from __future__ import annotations

import codecs
import collections
import os
import re
import sys

SUITES = [
    "tests/src/test/scala/com/nvidia/spark/rapids/"
    "RegularExpressionTranspilerSuite.scala",
    "tests/src/test/scala/com/nvidia/spark/rapids/"
    "RegularExpressionParserSuite.scala",
    "tests/src/test/scala/com/nvidia/spark/rapids/"
    "RegularExpressionSuite.scala",
]


def extract_corpus(ref_root: str):
    """Quoted string literals from the suites that compile as regexes."""
    pats = set()
    for rel in SUITES:
        path = os.path.join(ref_root, rel)
        if not os.path.exists(path):
            continue
        src = open(path, encoding="utf-8").read()
        for m in re.finditer(r'"((?:[^"\\]|\\.)*)"', src):
            raw = m.group(1)
            if not raw or len(raw) > 80:
                continue
            try:  # Scala string escapes -> actual chars (\\d -> \d, ...)
                lit = codecs.decode(raw, "unicode_escape")
            except Exception:
                continue
            if not lit.strip():
                continue
            if "${" in lit:      # Scala string-interpolation fragment,
                continue         # not a regex pattern
            try:
                re.compile(lit)
            except re.error:
                continue
            # skip obvious prose (sentences from assertion messages)
            if " " in lit and not any(c in lit for c in r"\[](){}|+*?^$."):
                continue
            pats.add(lit)
    return sorted(pats)


def measure(patterns):
    from spark_rapids_tpu.ops.regex_engine import (RegexUnsupported,
                                                   compile_regex)
    results = {}
    for mode, kwargs in [("rlike", {"search_prefix": True}),
                         ("extent", {"search_prefix": False,
                                     "extent_exact": True})]:
        ok = 0
        reasons = collections.Counter()
        fails = collections.defaultdict(list)
        for p in patterns:
            try:
                compile_regex(p, **kwargs)
                ok += 1
            except RegexUnsupported as e:
                key = _bucket(str(e))
                reasons[key] += 1
                if len(fails[key]) < 5:
                    fails[key].append(p)
            except Exception as e:  # parser crash = a bug, count separately
                reasons[f"CRASH {type(e).__name__}"] += 1
                if len(fails[f"CRASH {type(e).__name__}"]) < 5:
                    fails[f"CRASH {type(e).__name__}"].append(p)
        results[mode] = (ok, reasons, fails)
    return results


def _bucket(msg: str) -> str:
    msg = re.sub(r" at \d+ in .*$", "", msg)
    return msg[:70]


def main():
    ref_root = sys.argv[1] if len(sys.argv) > 1 else "/root/reference"
    patterns = extract_corpus(ref_root)
    results = measure(patterns)
    lines = ["# Device regex coverage",
             "",
             f"Corpus: {len(patterns)} valid patterns extracted from the "
             "reference's regex test suites "
             "(RegularExpressionTranspilerSuite & co).", ""]
    for mode, (ok, reasons, fails) in results.items():
        pct = 100.0 * ok / max(len(patterns), 1)
        lines.append(f"## mode `{mode}`: {ok}/{len(patterns)} "
                     f"on device ({pct:.1f}%)")
        lines.append("")
        lines.append("| rejection reason | count | examples |")
        lines.append("|---|---|---|")
        for reason, count in reasons.most_common():
            ex = ", ".join(f"`{p}`".replace("|", "\\|")
                           for p in fails[reason][:3])
            lines.append(f"| {reason.replace('|', chr(92)+'|')} "
                         f"| {count} | {ex} |")
        lines.append("")
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "regex_coverage.md")
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    for mode, (ok, _r, _f) in results.items():
        print(f"{mode}: {ok}/{len(patterns)} "
              f"({100.0 * ok / max(len(patterns), 1):.1f}%)")
    print("wrote", out)


if __name__ == "__main__":
    main()
