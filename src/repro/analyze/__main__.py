"""CLI: ``python -m repro.analyze [--hlo] [--table] [--json PATH]
[--update-baseline] [--root DIR]``.

Layer 1 (AST lint + repo invariants) always runs and never imports the
checked code. ``--hlo`` adds layer 2: before jax is imported the CLI
forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (unless the
caller already set XLA_FLAGS) and ``JAX_PLATFORMS=cpu``, so the protocol
mesh audits run genuinely multi-device on CPU, even on a machine with a TPU.
Exit status 1 iff any finding is neither inline-suppressed nor in the
committed baseline — the ``make lint`` contract.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analyze",
        description="repo-invariant lint (layer 1) + compiled-artifact "
                    "audit (layer 2, --hlo)")
    ap.add_argument("--hlo", action="store_true",
                    help="also run the HLO-scope rules (imports jax on a "
                         "forced 8-device CPU topology)")
    ap.add_argument("--table", action="store_true",
                    help="print the rule table (README format) and exit")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the findings report JSON here")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite results/analyze/baseline.json from the "
                         "current findings, pruning stale entries and "
                         "keeping scopes not run (keep it short; prefer "
                         "fixes)")
    ap.add_argument("--fast", action="store_true",
                    help="lint only the git-changed files (file-scope "
                         "rules) and scope the interprocedural taint "
                         "analysis to their call-graph component — the "
                         "`make lint-fast` pre-commit lane")
    ap.add_argument("--root", default=None,
                    help="repo root (default: cwd, or the checkout "
                         "containing this package)")
    args = ap.parse_args(argv)

    if args.hlo:
        # must precede any jax import anywhere in the process. The audit is
        # a CPU-topology audit by design: pinning the platform also keeps
        # this process off an accelerator that another process holds.
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        os.environ["JAX_PLATFORMS"] = "cpu"

    from . import findings as F
    from . import registry
    from .astlint import lint_paths, lint_repo

    if args.table:
        print(registry.markdown_table())
        return 0

    root = args.root or _find_root()
    changed = _changed_files(root) if args.fast else None
    if args.fast:
        from .rules import taint_byz
        taint_byz.scope_to(changed)
    found = lint_repo(root, only_files=changed)
    scopes = {"file", "repo"}
    if args.hlo:
        scopes.add("hlo")
        for rule in registry.rules(scope="hlo"):
            found.extend(rule.check(root))

    baseline = F.load_baseline(os.path.join(root, F.BASELINE_PATH))
    new, known = F.split_baselined(found, baseline)
    stats = {"rules_run": [r.rule_id for r in registry.rules()
                           if r.scope in scopes],
             "files_linted": len(lint_paths(root)),
             "hlo": bool(args.hlo)}

    if args.update_baseline:
        rule_scopes = {r.rule_id: r.scope for r in registry.rules()}
        path, pruned = F.refresh_baseline(
            found, os.path.join(root, F.BASELINE_PATH), root, scopes,
            rule_scopes)
        note = f" ({len(pruned)} stale entries pruned)" if pruned else ""
        print(f"baseline: {len(found)} finding(s) -> {path}{note}")
        return 0

    if args.json:
        F.write_report(F.to_report(new, known, stats), args.json)

    for f in new:
        print(f.format())
    if known:
        print(f"({len(known)} baselined finding(s) suppressed)")
    if new:
        print(f"\n{len(new)} violation(s)"
              + ("" if args.hlo else " (layer 1 only; --hlo for layer 2)"))
        return 1
    print("clean"
          + ("" if args.hlo else " (layer 1 only; --hlo for layer 2)"))
    return 0


def _changed_files(root: str) -> set[str] | None:
    """Rel paths changed vs HEAD (`--fast` scope); None -> full analysis."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"],
            cwd=root, capture_output=True, text=True, check=True,
            timeout=10).stdout
    except Exception:
        return None
    return {ln.strip() for ln in out.splitlines()
            if ln.strip().endswith(".py")}


def _find_root() -> str:
    """cwd if it holds the lint roots, else the checkout above src/."""
    cwd = os.getcwd()
    if os.path.isdir(os.path.join(cwd, "src", "repro")):
        return cwd
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


if __name__ == "__main__":
    sys.exit(main())
