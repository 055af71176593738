"""Command-line front end: ``python -m tools.hvdlint [paths...]``.

Exit codes: 0 clean, 1 live findings (or envdoc drift), 2 bad usage /
internal error — so CI can distinguish "violations" from "lint broke".
"""

import argparse
import json
import os
import sys

from . import envdoc
from .engine import analyze_paths, render_baseline

DEFAULT_PATHS = ["horovod_tpu", "tools", "examples"]
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json")
CONCURRENCY_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "concurrency_baseline.json")


def _build_parser():
    p = argparse.ArgumentParser(
        prog="python -m tools.hvdlint",
        description="distributed-correctness lint for horovod_tpu "
                    "(rules HVD001..HVD009; HVD000 = lint integrity)")
    p.add_argument("paths", nargs="*", default=None,
                   help="files/dirs to scan (default: %s)" %
                        " ".join(DEFAULT_PATHS))
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--explain", metavar="HVDnnn",
                   help="print the rule catalog entry (with the "
                        "historical bug it encodes) and exit")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file (default: %(default)s); "
                        "'none' disables")
    p.add_argument("--write-baseline", action="store_true",
                   help="write current live findings to the baseline "
                        "file (reasons left empty for a human to fill) "
                        "and exit")
    p.add_argument("--show-suppressed", action="store_true",
                   help="also print inline-/baseline-suppressed "
                        "findings")
    p.add_argument("--emit-envdoc", nargs="?", metavar="PATH",
                   const=envdoc.DEFAULT_DOC_PATH, default=None,
                   help="generate docs/envvars.md from ENV_REGISTRY "
                        "and exit")
    p.add_argument("--check-envdoc", action="store_true",
                   help="fail (exit 1) if docs/envvars.md drifted from "
                        "ENV_REGISTRY")
    p.add_argument("--concurrency", action="store_true",
                   help="run the whole-program lock-discipline pass "
                        "(HVD021/HVD022) instead of the per-file rules; "
                        "baseline defaults to concurrency_baseline.json")
    p.add_argument("--selftest", action="store_true",
                   help="run the concurrency pass over embedded "
                        "fixtures with known verdicts and exit — the "
                        "CI smoke that a crash in the pass fails loud")
    return p


def _explain(code):
    from .rules import RULES
    from .concurrency import EXPLAIN as CONCURRENCY_EXPLAIN
    code = code.upper()
    if code == "HVD000":
        print("HVD000 — lint integrity\n\nNot a code rule: reports "
              "problems with the lint inputs themselves — files that "
              "do not parse, reasonless `# hvdlint: disable=` "
              "comments, baseline entries with no reason, and stale "
              "baseline entries whose violation no longer exists.")
        return 0
    if code in CONCURRENCY_EXPLAIN:
        print(CONCURRENCY_EXPLAIN[code])
        return 0
    rule = RULES.get(code)
    if rule is None:
        print(f"unknown rule {code!r}; known: "
              f"{', '.join(sorted(RULES))}", file=sys.stderr)
        return 2
    print(rule.explain)
    return 0


def main(argv=None):
    args = _build_parser().parse_args(argv)

    if args.explain:
        return _explain(args.explain)

    if args.emit_envdoc is not None:
        entries = envdoc.load_env_registry()
        path = envdoc.write_doc(entries, args.emit_envdoc)
        print(f"wrote {path} ({len(entries)} variables)")
        return 0

    if args.check_envdoc:
        entries = envdoc.load_env_registry()
        problem = envdoc.check_doc(entries)
        if problem:
            print(f"hvdlint: {problem}", file=sys.stderr)
            return 1
        print(f"docs/envvars.md matches ENV_REGISTRY "
              f"({len(entries)} variables)")
        return 0

    if args.selftest:
        from .concurrency import selftest
        problem = selftest()
        if problem:
            print(f"hvdlint: {problem}", file=sys.stderr)
            return 1
        print("hvdlint: concurrency selftest passed "
              "(HVD021+HVD022 fire on the bad fixture, "
              "clean fixture stays clean)")
        return 0

    program_pass = None
    rules = None
    if args.concurrency:
        from .concurrency import run_pass
        program_pass = run_pass
        rules = {}  # the per-file rules run in the default invocation
        if args.baseline == DEFAULT_BASELINE:
            args.baseline = CONCURRENCY_BASELINE

    paths = args.paths or DEFAULT_PATHS
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"hvdlint: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2
    baseline = None if args.baseline == "none" else args.baseline

    if args.write_baseline:
        findings, _ = analyze_paths(paths, baseline_path=None,
                                    rules=rules,
                                    program_pass=program_pass)
        live = [f for f in findings if not f.suppressed]
        data = render_baseline(live)
        with open(args.baseline, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.baseline}: {len(data['entries'])} entries "
              f"covering {len(live)} finding(s) — now fill in every "
              "empty \"reason\"")
        return 0

    findings, files = analyze_paths(paths, baseline_path=baseline,
                                    rules=rules,
                                    program_pass=program_pass)
    live = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]

    if args.format == "json":
        shown = findings if args.show_suppressed else live
        print(json.dumps({
            "files_scanned": len(files),
            "live": len(live),
            "suppressed": len(suppressed),
            "findings": [f.as_dict() for f in shown],
        }, indent=2))
    else:
        shown = findings if args.show_suppressed else live
        for f in shown:
            tag = f" [suppressed:{f.suppressed}]" if f.suppressed else ""
            print(f.format() + tag)
        tail = (f"hvdlint: {len(files)} files, {len(live)} finding(s)"
                f", {len(suppressed)} suppressed")
        print(tail, file=sys.stderr)
    return 1 if live else 0
