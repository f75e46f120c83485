"""tpulint CLI — scriptable gate in the tools/obs_check.py style.

Exit codes: 0 = clean, 1 = findings, 2 = internal/usage error.

Run as ``python -m tools.tpulint [paths...]`` or directly as
``python tools/tpulint/cli.py [paths...]`` from the repo root.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):  # direct-file invocation
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from tools.tpulint import config  # noqa: E402
from tools.tpulint.analyzer import (  # noqa: E402
    Finding,
    analyze_project,
)


def iter_py_files(paths: list[str]) -> list[Path]:
    files: list[Path] = []
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        elif p.suffix == ".py" and p.is_file():
            files.append(p)
        else:
            raise FileNotFoundError(f"not a python file or directory: {raw}")
    return files


def _report_text(findings: list[Finding], n_files: int, verbose: bool) -> None:
    active = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]
    for f in active:
        print(f.render())
    if verbose:
        for f in suppressed:
            print(f.render())
    print(
        f"tpulint: {len(active)} finding(s), {len(suppressed)} "
        f"suppressed-with-reason, across {n_files} file(s)"
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tpulint",
        description="AST hazard analyzer for JAX/TPU serving code "
                    "(recompile / host-sync / async-blocking).",
    )
    parser.add_argument(
        "paths", nargs="*", default=["vllm_tgis_adapter_tpu"],
        help="files or directories to analyze (default: the package)",
    )
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the rule table and exit 0",
    )
    parser.add_argument(
        "--write-lattice", action="store_true",
        help="regenerate tools/tpulint/lattice_manifest.json from the "
             "given paths (after an INTENTIONAL jit-entry change) "
             "and exit 0",
    )
    parser.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json includes suppressed findings)",
    )
    parser.add_argument(
        "-v", "--verbose", action="store_true",
        help="also print suppressed findings",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for code, desc in sorted(config.RULES.items()):
            print(f"{code}  {desc}")
        return 0

    try:
        files = iter_py_files(args.paths or ["vllm_tgis_adapter_tpu"])
    except FileNotFoundError as e:
        print(f"tpulint: {e}", file=sys.stderr)
        return 2

    if args.write_lattice:
        from tools.tpulint.lattice import write_manifest

        target = write_manifest([Path(p) for p in args.paths])
        print(f"tpulint: wrote compile-lattice manifest to {target}")
        return 0

    try:
        findings: list[Finding] = analyze_project(files)
    except SyntaxError as e:
        print(f"tpulint: cannot parse: {e}", file=sys.stderr)
        return 2

    if args.format == "json":
        print(json.dumps(
            [dataclass_dict(f) for f in findings], indent=2
        ))
    else:
        _report_text(findings, len(files), args.verbose)
    return 1 if any(not f.suppressed for f in findings) else 0


def dataclass_dict(f: Finding) -> dict:
    return {
        "path": f.path, "line": f.line, "col": f.col, "code": f.code,
        "message": f.message, "suppressed": f.suppressed,
        "reason": f.reason,
    }


if __name__ == "__main__":
    raise SystemExit(main())
