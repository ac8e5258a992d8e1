"""Unified ``kvmini-tpu`` CLI.

The reference wraps its stages behind a single console script that dispatches
to per-stage scripts via subprocess (/root/reference/kvmini/cli.py:30-150).
Here every stage is an importable module with a ``register(parser)`` /
``run(args)`` pair, dispatched in-process — no shelling out, no flag
reconstruction.

Dispatch is genuinely lazy: only the chosen subcommand's module is imported,
so ``kvmini-tpu analyze`` never pays the JAX/libtpu import that
``kvmini-tpu serve`` needs, and a broken stage module breaks only its own
subcommand.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import traceback
from typing import Optional, Sequence

# subcommand -> (module, help). Each module exposes
#   register(parser: argparse.ArgumentParser) -> None   (optional)
#   run(args: argparse.Namespace) -> int
_SUBCOMMANDS: dict[str, tuple[str, str]] = {
    "loadtest": ("kserve_vllm_mini_tpu.loadgen.runner", "Generate load against an endpoint"),
    "analyze": ("kserve_vllm_mini_tpu.analysis.analyzer", "requests.csv -> results.json metrics"),
    "cost": ("kserve_vllm_mini_tpu.costs.estimator", "Attribute cost from resource-seconds x pricing"),
    "cost-simple": ("kserve_vllm_mini_tpu.costs.simple", "Back-of-envelope $/1K tokens from latency x chip price"),
    "energy": ("kserve_vllm_mini_tpu.energy.collector", "Collect/integrate chip power into Wh metrics"),
    "report": ("kserve_vllm_mini_tpu.report.html", "Render HTML report from results.json / sweep CSVs"),
    "plan": ("kserve_vllm_mini_tpu.costs.planner", "Capacity planning: chips for target RPS at SLO"),
    "gate": ("kserve_vllm_mini_tpu.gates.slo", "Pass/fail results against SLO budgets"),
    "canary": ("kserve_vllm_mini_tpu.gates.canary", "Compare candidate vs baseline run"),
    "serve": ("kserve_vllm_mini_tpu.runtime.server", "Start the in-repo JAX serving runtime"),
    "bench": ("kserve_vllm_mini_tpu.bench_pipeline", "Full pipeline: validate -> load -> analyze -> cost"),
    "validate": ("kserve_vllm_mini_tpu.core.validate", "Pre-flight config validation"),
    "quality": ("kserve_vllm_mini_tpu.quality.evaluator", "Run the mini quality-eval suite"),
    "sweep": ("kserve_vllm_mini_tpu.sweeps.runner", "Run a parameter sweep"),
    "compare": ("kserve_vllm_mini_tpu.compare.backends", "A/B/C compare serving backends"),
    "parity": ("kserve_vllm_mini_tpu.compare.parity", "OpenAI API conformance probe"),
    "fairness": ("kserve_vllm_mini_tpu.compare.fairness", "Dual-tenant fairness/backpressure run"),
    "bundle": ("kserve_vllm_mini_tpu.provenance.bundle", "Create a signed reproducible artifact bundle"),
    "deploy": ("kserve_vllm_mini_tpu.deploy.manifests", "Render/apply KServe TPU manifests"),
    "probe": ("kserve_vllm_mini_tpu.probes.net_storage", "Network/storage IO probe"),
    "cache-probe": ("kserve_vllm_mini_tpu.probes.cache", "Infer prompt-cache hit ratio from TTFT deltas"),
    "preflight": ("kserve_vllm_mini_tpu.deploy.preflight", "Cluster/local environment checks"),
    "facts": ("kserve_vllm_mini_tpu.provenance.facts", "Collect cluster/local provenance facts"),
    "matrix": ("kserve_vllm_mini_tpu.matrix.runner", "GA-hardening reference matrix run"),
    "compile-sweep": ("kserve_vllm_mini_tpu.sweeps.compile_perf", "AOT compile-time vs serving-perf tradeoff"),
    "chaos": ("kserve_vllm_mini_tpu.chaos.harness", "Fault injection + MTTR measurement"),
    "profile": ("kserve_vllm_mini_tpu.profiling.capture", "Capture a TensorBoard trace of a live runtime"),
    "autoscale-controller": ("kserve_vllm_mini_tpu.autoscale.controller",
                             "SLO/duty-signal-driven replica controller"),
    "fleet": ("kserve_vllm_mini_tpu.fleet.service",
              "N serving replicas behind the cache-aware router "
              "(+ optional live local autoscaler)"),
    "autoscale-sim": ("kserve_vllm_mini_tpu.autoscale.simulate",
                      "Replay a load timeline against the autoscale policy"),
}


def _help_text() -> str:
    lines = [
        "usage: kvmini-tpu COMMAND [options]",
        "",
        "TPU-native LLM serving benchmark + runtime framework",
        "",
        "commands:",
    ]
    for name, (_, help_text) in sorted(_SUBCOMMANDS.items()):
        lines.append(f"  {name:<10} {help_text}")
    lines.append("")
    lines.append("run 'kvmini-tpu COMMAND --help' for command options")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(_help_text())
        return 0 if argv else 2
    command, rest = argv[0], argv[1:]
    if command not in _SUBCOMMANDS:
        print(f"kvmini-tpu: unknown command {command!r}\n\n{_help_text()}", file=sys.stderr)
        return 2
    module_name, help_text = _SUBCOMMANDS[command]
    try:
        mod = importlib.import_module(module_name)
    except Exception:
        print(
            f"kvmini-tpu: subcommand '{command}' is unavailable "
            f"({module_name} failed to import):\n{traceback.format_exc(limit=1)}",
            file=sys.stderr,
        )
        return 2
    parser = argparse.ArgumentParser(prog=f"kvmini-tpu {command}", description=help_text)
    register = getattr(mod, "register", None)
    if register is not None:
        register(parser)
    run = getattr(mod, "run", None)
    if run is None:
        print(f"kvmini-tpu: subcommand '{command}' has no runner yet", file=sys.stderr)
        return 2
    args = parser.parse_args(rest)
    try:
        return int(run(args) or 0)
    except FileNotFoundError as e:
        print(f"kvmini-tpu {command}: file not found: {e}", file=sys.stderr)
        return 1
    except BrokenPipeError:
        # Output pipe (head/less) closed early. Exit 141 (128+SIGPIPE), never
        # 0 — a truncated gate/canary verdict must not read as a pass.
        try:
            sys.stdout.close()
        except Exception:
            pass
        return 141


if __name__ == "__main__":
    raise SystemExit(main())
