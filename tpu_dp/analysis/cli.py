"""dplint CLI: `python -m tpu_dp.analysis [paths...]` / `tools/dplint.py`.

Runs three levels over the given paths:

- **Level 1 (AST)**: DP101–DP104, the donation check (DP204), and the
  retrace-hazard lint (DP305). No jax import.
- **Level 2 (jaxpr, unless --no-jaxpr)**: the gradient-sync pass
  (DP201–DP203). When the analyzed tree contains the shipped step factory
  (`tpu_dp/train/step.py`), the real per-shard step is traced and verified
  for every `--accum-steps` variant; a standalone .py defining
  `DPLINT_LOCAL_STEP` is imported and its step verified the same way.
- **Level 4 (host protocol, via the `host` subcommand)**: DP401–DP405
  (`tpu_dp.analysis.hostproto`) — IO-seam routing, unbounded polls,
  wall-clock deadlines, flightrec kind and counter name drift. Runs as
  `python -m tpu_dp.analysis host [paths...]`; pure AST, no jax.
- **Level 5 (concurrency, via the `conc` subcommand)**: DP501–DP505
  (`tpu_dp.analysis.concurrency`) — per-attribute locksets, lock-order
  cycles, rank-gated collective-participation divergence, thread
  lifecycles, locks held across blocking calls. Runs as
  `python -m tpu_dp.analysis conc [paths...]`; pure AST, no jax.
- **Level 3 (HLO, unless --no-hlo)**: the compiled-artifact pass
  (DP301–DP304). The shipped step programs are lowered and compiled on an
  abstract `--world`-device data mesh and the optimized HLO is verified
  (collective classification, host transfers, input_output_alias, schedule
  fingerprint — the fingerprint artifact lands at `--fingerprint-out`);
  a standalone .py defining `DPLINT_HLO_PROGRAM` rides the same pipeline.

Exit codes: 0 clean, 1 findings, 2 internal/usage error. On an internal
error the findings already collected are still rendered to stdout (marked
partial) and the traceback goes to stderr, so `--json` output stays
machine-parseable. `--baseline FILE` suppresses findings by stable
fingerprint (rule+path+symbol — never line numbers), letting CI adopt new
rules without blocking on pre-existing findings; `--write-baseline FILE`
records the current findings as that file. The tier-1 CI lane
(`tools/run_tier1.sh --dplint`) fails on any unsuppressed finding.
"""

from __future__ import annotations

import argparse
import ast
import importlib.util
import os
import sys

from tpu_dp.analysis import astlint, coupling, donation, pragmas, recompile
from tpu_dp.analysis.report import (
    Finding,
    apply_baseline,
    list_rules,
    load_baseline,
    render_json,
    render_text,
    write_baseline,
)

_STEP_HOOK = "DPLINT_LOCAL_STEP"
_HLO_HOOK = "DPLINT_HLO_PROGRAM"


def _module_hooks(path: str, source: str) -> set[str]:
    """Which dplint hooks (`DPLINT_*`) a file defines at top level."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return set()
    hooks: set[str] = set()
    wanted = {_STEP_HOOK, _HLO_HOOK}
    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [
                node.target
            ]
            for t in targets:
                if isinstance(t, ast.Name) and t.id in wanted:
                    hooks.add(t.id)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in wanted:
                hooks.add(node.name)
    return hooks


def _load_module(path: str):
    name = "_dplint_fixture_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _verify_step_hook(path: str, module, world: int) -> list[Finding]:
    from tpu_dp.analysis import gradsync

    hook = getattr(module, _STEP_HOOK)
    built = hook() if callable(hook) else hook
    fn, example_args = built[0], built[1]
    hook_world = built[2] if len(built) > 2 else world
    findings, _ = gradsync.verify_local_step(
        fn, example_args, world=hook_world,
        where=(path, fn.__code__.co_firstlineno),
        label=f"{_STEP_HOOK} in {os.path.basename(path)}",
    )
    return findings


def _setup_backend(world: int) -> None:
    """Pin the analysis backend: CPU with ``world`` virtual devices.

    Must run before the first jax backend initialization; in-process
    callers (pytest via conftest) have already done the same trick. When
    the user explicitly targets a real platform (JAX_PLATFORMS set), it is
    left alone.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={world}"
        ).strip()


def _ast_level_main(argv: list[str], *, prog: str, description: str,
                    rule_prefix: str, lint_paths) -> int:
    """Shared driver for the pure-AST subcommand levels (4: ``host``,
    5: ``conc``): paths / --json / --baseline / --write-baseline /
    --list-rules over the given ``lint_paths`` pass, with the same
    report/baseline/pragma machinery and exit codes as the main driver.
    No jax import anywhere on this path."""
    parser = argparse.ArgumentParser(prog=prog, description=description)
    parser.add_argument("paths", nargs="*", default=None,
                        help="files or directories to analyze "
                             "(default: the tpu_dp package)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable output")
    parser.add_argument("--baseline", default=None, metavar="FILE",
                        help="suppress findings whose fingerprint "
                             "(rule+path+symbol) appears in FILE")
    parser.add_argument("--write-baseline", default=None, metavar="FILE",
                        help="write the current findings' fingerprints to "
                             "FILE and exit 0")
    parser.add_argument("--list-rules", action="store_true",
                        help=f"print the {rule_prefix}xx rule table and "
                             f"exit")
    args = parser.parse_args(argv)

    from tpu_dp.analysis.report import RULES

    if args.list_rules:
        lines = []
        for rule, (title, failure) in RULES.items():
            if rule.startswith(rule_prefix):
                lines.append(f"{rule}  {title}")
                lines.append(f"       {failure}")
        print("\n".join(lines))
        return 0

    suppressed: set[str] = set()
    if args.baseline is not None:
        try:
            suppressed = load_baseline(args.baseline)
        except (OSError, ValueError) as e:
            print(f"dplint: bad --baseline: {e}", file=sys.stderr)
            return 2

    paths = args.paths or [os.path.join(_repo_root(), "tpu_dp")]
    findings: list[Finding] = []
    internal_error: str | None = None
    try:
        findings = lint_paths(paths)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print("dplint: internal error (partial findings on stdout)",
              file=sys.stderr)
        internal_error = f"{type(e).__name__}: {e}"

    all_findings = findings
    findings = apply_baseline(findings, suppressed)
    if args.write_baseline is not None:
        if internal_error:
            print("dplint: refusing to write baseline from partial "
                  "findings (internal error above)", file=sys.stderr)
            print(render_json(findings, error=internal_error) if args.json
                  else render_text(findings, error=internal_error))
            return 2
        n = write_baseline(args.write_baseline, all_findings)
        print(f"dplint: wrote {n} fingerprint(s) to {args.write_baseline}",
              file=sys.stderr)
        return 0

    print(render_json(findings, error=internal_error) if args.json
          else render_text(findings, error=internal_error))
    if internal_error:
        return 2
    return 1 if findings else 0


def host_main(argv: list[str]) -> int:
    """`python -m tpu_dp.analysis host [paths...]`: the Level-4 pass.

    Runs only DP401–DP405 (`tpu_dp.analysis.hostproto`) — pure AST, no
    jax, no tracing — over the given paths (default: the whole tpu_dp
    package, so DP404's rendered-kind-is-emitted check sees the real
    emit sites in train/ and utils/, not just the protocol packages the
    findings are scoped to).
    """
    from tpu_dp.analysis import hostproto

    return _ast_level_main(
        argv, prog="dplint host",
        description="host-protocol static analysis (DP401-DP405): "
                    "IO-seam routing, unbounded polls, wall-clock "
                    "deadlines, flightrec kind and counter name drift",
        rule_prefix="DP4", lint_paths=hostproto.lint_paths,
    )


def conc_main(argv: list[str]) -> int:
    """`python -m tpu_dp.analysis conc [paths...]`: the Level-5 pass.

    Runs only DP501–DP505 (`tpu_dp.analysis.concurrency`) — pure AST,
    no jax — over the given paths (default: the whole tpu_dp package;
    the rules self-scope to the threaded host modules).
    """
    from tpu_dp.analysis import concurrency

    return _ast_level_main(
        argv, prog="dplint conc",
        description="concurrency & collective-participation static "
                    "analysis (DP501-DP505): locksets, lock-order "
                    "cycles, rank-gated participation divergence, "
                    "thread lifecycles, locks held across blocking "
                    "calls",
        rule_prefix="DP5", lint_paths=concurrency.lint_paths,
    )


def main(argv: list[str] | None = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    # `dplint host ...` / `dplint conc ...` dispatch to the pure-AST
    # Level-4/Level-5 passes before the device-program parser sees the
    # argv (they have their own flag surface and never touch jax).
    if argv and argv[0] == "host":
        return host_main(argv[1:])
    if argv and argv[0] == "conc":
        return conc_main(argv[1:])
    parser = argparse.ArgumentParser(
        prog="dplint",
        description="static SPMD-correctness analyzer for tpu_dp "
                    "(collective-deadlock, gradient-sync, and compiled-"
                    "artifact verifier)",
    )
    parser.add_argument("paths", nargs="*", default=None,
                        help="files or directories to analyze "
                             "(default: the tpu_dp package)")
    parser.add_argument("--no-jaxpr", action="store_true",
                        help="skip the Level-2 jaxpr gradient-sync pass")
    parser.add_argument("--no-hlo", action="store_true",
                        help="skip the Level-3 compiled-HLO pass")
    parser.add_argument("--accum-steps", default="1,2",
                        help="comma-separated accum_steps variants the "
                             "jaxpr/HLO passes verify (default: 1,2)")
    parser.add_argument("--world", type=int, default=8,
                        help="abstract data-axis size for tracing/lowering")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable output")
    parser.add_argument("--baseline", default=None, metavar="FILE",
                        help="suppress findings whose fingerprint "
                             "(rule+path+symbol) appears in FILE")
    parser.add_argument("--write-baseline", default=None, metavar="FILE",
                        help="write the current findings' fingerprints to "
                             "FILE and exit 0")
    parser.add_argument("--fingerprint-out", default=None, metavar="FILE",
                        help="where the Level-3 collective-schedule "
                             "fingerprint artifact lands (default: "
                             "<repo>/artifacts/collective_fingerprint.json; "
                             "'none' disables)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule table and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        print(list_rules())
        return 0

    # Usage errors are diagnosed before any analysis runs: a clean message
    # on stderr and exit 2, never a traceback dressed as an internal error.
    try:
        accum_variants = _parse_accum(args.accum_steps)
    except ValueError as e:
        print(f"dplint: bad --accum-steps: {e}", file=sys.stderr)
        return 2
    suppressed: set[str] = set()
    if args.baseline is not None:
        try:
            suppressed = load_baseline(args.baseline)
        except (OSError, ValueError) as e:
            print(f"dplint: bad --baseline: {e}", file=sys.stderr)
            return 2

    paths = args.paths or [os.path.join(_repo_root(), "tpu_dp")]

    findings: list[Finding] = []
    internal_error: str | None = None
    sources: dict[str, str] = {}
    try:
        # One read per file; AST lint, donation check, retrace lint, and
        # hook discovery all work from the same source text.
        files = astlint.iter_py_files(paths)
        hooks: dict[str, set[str]] = {}
        for f in files:
            with open(f, encoding="utf-8") as fh:
                sources[f] = fh.read()
            findings.extend(astlint.lint_source(f, sources[f]))
            findings.extend(coupling.lint_source(f, sources[f]))
            findings.extend(donation.check_source(f, sources[f]))
            findings.extend(recompile.lint_source(f, sources[f]))
            hooks[f] = _module_hooks(f, sources[f])

        has_repo_step = any(
            f.replace(os.sep, "/").endswith("tpu_dp/train/step.py")
            for f in files
        )

        # A hook module is imported only when a pass that consumes it will
        # actually run: --no-jaxpr must skip DPLINT_LOCAL_STEP-only files
        # entirely (not execute their import and crash), and likewise
        # --no-hlo for DPLINT_HLO_PROGRAM-only files.
        def _wanted(f: str) -> bool:
            return ((not args.no_jaxpr and _STEP_HOOK in hooks[f])
                    or (not args.no_hlo and _HLO_HOOK in hooks[f]))

        modules: dict[str, object] = {}
        if (not (args.no_jaxpr and args.no_hlo) and has_repo_step) or any(
            _wanted(f) for f in files
        ):
            _setup_backend(args.world)
            modules = {f: _load_module(f) for f in files if _wanted(f)}

        if not args.no_jaxpr:
            if has_repo_step:
                from tpu_dp.analysis import gradsync

                # Every legal update schedule: the replicated gradient
                # pmean, the sharded reduce-scatter path
                # (train.update_sharding), the quantized int8 wire
                # (train.collective_dtype=int8 — the payload all_to_all is
                # the counted reduction), and the bucketed overlap
                # schedule (train.bucket_mb — each leaf reduces inside its
                # bucket's concatenated exchange) each carry the
                # exactly-one-reduction-per-leaf contract.
                for accum in accum_variants:
                    for mode, wire, bucket in (
                        ("replicated", None, 0.0),
                        ("sharded", None, 0.0),
                        ("sharded", "int8", 0.0),
                        ("sharded", None, 0.05),
                        ("sharded", "int8", 0.05),
                    ):
                        got, _ = gradsync.verify_repo_step(
                            accum_steps=accum, world=args.world,
                            update_sharding=mode, collective_dtype=wire,
                            bucket_mb=bucket,
                        )
                        findings.extend(got)
            for f in files:
                if _STEP_HOOK in hooks[f]:
                    findings.extend(
                        _verify_step_hook(f, modules[f], args.world)
                    )

        if not args.no_hlo:
            findings.extend(_run_hlo_pass(
                args, files, hooks, modules, has_repo_step, accum_variants,
            ))
    except Exception as e:
        import traceback

        traceback.print_exc()
        print("dplint: internal error (partial findings on stdout)",
              file=sys.stderr)
        internal_error = f"{type(e).__name__}: {e}"

    # The trace-level passes (jaxpr/HLO hooks) honor the same allow-pragma
    # machinery as the AST passes: a pragma on the finding's attributed
    # line — the hook program's `def` line — suppresses it. The AST rules
    # already self-filtered with their own (wider) extra-line placement,
    # so re-checking the bare line here is a no-op for them.
    findings = _apply_pragmas(findings, sources)

    # The baseline is written from the PRE-suppression findings: the
    # natural in-place refresh `--baseline ci.json --write-baseline ci.json`
    # must re-record the still-present findings, not empty the file.
    all_findings = findings
    findings = apply_baseline(findings, suppressed)

    if args.write_baseline is not None:
        if internal_error:
            # A truncated run would persist an under-suppressing baseline
            # that blocks the next healthy run; refuse.
            print("dplint: refusing to write baseline from partial "
                  "findings (internal error above)", file=sys.stderr)
            print(render_json(findings, error=internal_error) if args.json
                  else render_text(findings, error=internal_error))
            return 2
        n = write_baseline(args.write_baseline, all_findings)
        print(f"dplint: wrote {n} fingerprint(s) to {args.write_baseline}",
              file=sys.stderr)
        return 0

    print(render_json(findings, error=internal_error) if args.json
          else render_text(findings, error=internal_error))
    if internal_error:
        return 2
    return 1 if findings else 0


def _apply_pragmas(findings: list[Finding],
                   sources: dict[str, str]) -> list[Finding]:
    """Drop findings whose attributed line carries an allow-pragma for
    their rule, for files whose source this run already read."""
    cache: dict[str, dict[int, set[str]]] = {}
    out: list[Finding] = []
    for f in findings:
        src = sources.get(f.path)
        if src is not None:
            allowed = cache.get(f.path)
            if allowed is None:
                allowed = cache[f.path] = pragmas.collect(src)
            if pragmas.is_allowed(allowed, f.rule, (f.line,)):
                continue
        out.append(f)
    return out


def _run_hlo_pass(args, files, hooks, modules, has_repo_step,
                  accum_variants) -> list[Finding]:
    """Level 3: compiled-artifact verification (DP301–DP304)."""
    if not has_repo_step and not any(_HLO_HOOK in h for h in hooks.values()):
        return []
    import jax

    from tpu_dp.analysis import hlo

    if len(jax.devices()) < 2:
        # A 1-device backend compiles away every collective: DP301 would
        # report the gradient all-reduce missing on a correct program.
        print("dplint: skipping Level-3 HLO pass (backend has "
              f"{len(jax.devices())} device(s); needs >= 2 — run before "
              "jax initializes or pass XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)", file=sys.stderr)
        return []

    findings: list[Finding] = []
    if has_repo_step:
        got, artifact = hlo.verify_repo_hlo(
            accum_steps=accum_variants, world=args.world
        )
        findings.extend(got)
        out = args.fingerprint_out
        if out is None:
            out = os.path.join(_repo_root(), "artifacts",
                               "collective_fingerprint.json")
        if out and out.lower() != "none":
            hlo.write_fingerprint_artifact(out, artifact)
    for f in files:
        if _HLO_HOOK in hooks[f]:
            findings.extend(hlo.verify_hlo_hook(f, modules[f], args.world))
    return findings


def _parse_accum(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        part = part.strip()
        if part:
            n = int(part)
            if n < 1:
                raise ValueError(f"accum_steps must be >= 1, got {n}")
            out.append(n)
    return out or [1]


def _repo_root() -> str:
    # tpu_dp/analysis/cli.py -> repo root two levels above the package.
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
