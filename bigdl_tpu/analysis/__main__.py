"""graftlint CLI: ``python -m bigdl_tpu.analysis [options] [paths]``.

Exit codes (usable as a raw CI gate):

* ``0`` — clean: no findings outside the baseline.
* ``1`` — new findings (printed one per line, ``path:line: rule: ...``).
* ``2`` — ratchet violation on ``--update-baseline`` (a per-rule count
  would grow), or unparseable inputs.

``--update-baseline`` rewrites ``tools/graftlint_baseline.json`` from
the current findings but REFUSES to let any rule's count grow —
the baseline only ratchets down. ``--init-baseline`` bypasses the
ratchet once (bootstrapping a new checkout; review the diff).
"""

from __future__ import annotations

import argparse
import pathlib
import sys
from typing import List, Optional

from bigdl_tpu.analysis import core


def _repo_root() -> pathlib.Path:
    # bigdl_tpu/analysis/__main__.py -> repo root two levels up from
    # the package directory
    return pathlib.Path(__file__).resolve().parent.parent.parent


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint",
        description="JAX-hazard + lock-discipline static analysis "
                    "with a ratcheted baseline")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: the bigdl_tpu "
                         "package)")
    ap.add_argument("--baseline", type=pathlib.Path, default=None,
                    help="baseline JSON (default: "
                         "tools/graftlint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding (ignore the baseline)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(refused if any rule count would grow)")
    ap.add_argument("--init-baseline", action="store_true",
                    help="write the baseline without the ratchet check")
    ap.add_argument("--rule", action="append", default=None,
                    help="restrict to a rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, desc in core.RULES.items():
            print(f"{name:24s} {desc}")
        return 0

    root = _repo_root()
    baseline_path = args.baseline or root / "tools" / \
        "graftlint_baseline.json"

    if args.paths:
        files: List[pathlib.Path] = []
        for p in args.paths:
            path = pathlib.Path(p)
            if path.is_dir():
                files += core.iter_package_files(path)
            else:
                files.append(path)
    else:
        files = core.iter_package_files(root / "bigdl_tpu")

    result = core.analyze(files, repo_root=root, rules=args.rule)
    for bad in result.parse_failures:
        print(f"graftlint: cannot parse {bad}", file=sys.stderr)

    if args.update_baseline or args.init_baseline:
        old = core.load_baseline(baseline_path)
        if not args.init_baseline:
            violations = core.ratchet_violations(old, result.findings)
            if violations:
                print("graftlint: baseline update REFUSED "
                      "(ratchet: counts may only shrink):")
                for v in violations:
                    print(f"  {v}")
                return 2
        baseline_path.parent.mkdir(parents=True, exist_ok=True)
        baseline_path.write_text(
            core.render_baseline(result.findings), encoding="utf-8")
        print(f"graftlint: baseline written to {baseline_path} "
              f"({len(result.findings)} finding(s))")
        return 0

    if args.no_baseline:
        new = result.findings
    else:
        new = core.new_findings(result.findings,
                                core.load_baseline(baseline_path))
    for f in new:
        print(f.render())

    counts = result.counts()
    summary = ", ".join(f"{r}={n}" for r, n in sorted(counts.items()))
    print(f"graftlint: {len(result.findings)} finding(s) "
          f"({summary or 'none'}); {len(new)} new vs baseline; "
          f"{len(result.suppressed)} inline-suppressed; "
          f"{len(files)} file(s) scanned")
    if new:
        print("graftlint: FAIL — fix the finding, add an audited "
              "'# graftlint: disable=<rule>', or (for legacy debt) "
              "rebaseline with --update-baseline")
        return 1
    if result.parse_failures:
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
