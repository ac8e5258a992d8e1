#!/usr/bin/env python3
"""One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

A new process each time. It makes the weights from ``--seed``, boots the
program's served path in this process on the cell's chip
(``bench/systems/``), warms the cell's own prompt buckets and its decode
step through the load generator (``bench/client.py``, a process of its own),
offers the cell's traffic for ``--seconds`` seconds after a short lead-in,
reads the counters, frees the engine, decides ``correct`` against the plain
reference (``bench/check.py``) and prints one JSON object as its last line:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: every number ``correct``
compared, beside its limit (``bench/check.py lines_of`` renders the same as
the last lines of stderr; ``correct`` is that every one of them holds).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of about three seconds in the middle
of the window.

With no TPU, or fewer chips than the cell asks for, it exits with code 2 and
prints nothing to stdout; it never carries on on the CPU. ``--rehearse``
is the stated exception for debugging the harness in a sandbox: the
configuration's tiny sizes on the CPU, a last line that says
``"correct": false``, carries no metrics and names ``platform: cpu``.

Everything else worth keeping goes to ``chiprun_out/bench/<cell>/<seed>/``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import config as bench_config  # noqa: E402
from bench import metrics  # noqa: E402

TRACE_SECONDS = 3.0


def say(msg: str) -> None:
    print(msg, flush=True)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What a metric reader gets: the client's records, the window, counter
    deltas, memory, the reduced trace (``None`` without ``--trace 1``)."""

    def __init__(self, cell, records, window_s, run_end_s, setup_s, counters,
                 memory, trace, device_kind):
        self.cell, self.records, self.window_s = cell, records, window_s
        self.run_end_s, self.setup_s, self.counters = run_end_s, setup_s, counters
        self.memory, self.trace, self.device_kind = memory, trace, device_kind
        self.gaps_ms = metrics.token_gaps_ms(records, window_s)


def reader_name(name: str) -> str:
    """The file of ``bench/readers/`` that reads this metric: its own, or for
    a name with a suffix (``decode_step_ms.sat``: the same quantity under
    another name, where it moves another end-to-end metric or is recorded and
    not judged) the reader of its stem."""
    if "." in name and not (ROOT / "bench" / "readers" / f"{name}.py").exists():
        return name.rsplit(".", 1)[0]
    return name


def read_metric(name: str, ctx: Context):
    return bench_config.load_module("readers", reader_name(name)).read(ctx)


def scrape(url: str) -> dict[str, float]:
    """The program's exported counters and gauges, from ``/metrics``."""
    with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            parts = line.split()
            if len(parts) == 2:
                try:
                    out[parts[0]] = float(parts[1])
                except ValueError:
                    pass
    return out


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="debug the harness on the CPU at the tiny sizes; never correct")
    ap.add_argument("--rate-rps", type=float, default=None,
                    help="offer this rate instead of the cell's (the knee sweep)")
    ap.add_argument("--engine", action="append", default=[], metavar="KEY=VALUE",
                    help="override one engine setting (the lower-precision control)")
    return ap.parse_args(argv)


def collect(specs: list[dict], ctx: Context) -> dict:
    """Each of the cell's metrics from its reader; one that finds nothing to
    read is left out. A share over 100% is a fault of the yardstick, never a
    result: the run fails rather than print it."""
    out = {}
    for spec in specs:
        value = read_metric(spec["name"], ctx)
        if value is None:
            continue
        if spec["unit"] == "%" and value > 100.0:
            raise RuntimeError(f"{spec['name']} reads {value}% > 100%: the yardstick is wrong")
        out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def decide_correct(args, cell, records, plan, tokenizer, guards, window_compiles):
    """Every number ``correct`` compares, beside its limit, under a short name
    (``bench/check.py holds`` judges each, ``lines_of`` prints them): the sampled
    requests against the plain reference, exact token counts of every completed
    request, and the guards on compiles and kernels. With it, lines that say
    what was sampled and which request or executable was at fault."""
    from bench import check

    system = bench_config.load_module("systems", cell["config"]["model"].get("system", "engine"))
    model, info, compared, miscounted = cell["config"]["model"], [], {}, 0
    for r in records:
        if metrics.completed(r):
            n_out = len(system.served_token_ids(r["text"]))
            usage = r["usage"] or {}
            if (n_out != r["max_tokens"] or usage.get("completion_tokens") != r["max_tokens"]
                    or usage.get("prompt_tokens") != r["prompt_tokens"]):
                miscounted += 1
                info.append(f"info request {r['id']}: served {n_out} tokens "
                            f"(usage {usage}), asked {r['prompt_tokens']}+{r['max_tokens']}")
    picked = check.sample(records, args.seed, cell["traffic"]["check"])
    t_ref = time.monotonic()
    if picked:
        content = {r["id"]: r["content"] for r in plan["requests"]}
        seqs, first = [], []
        for r in picked:
            prompt = system.chat_prompt_ids(tokenizer, content[r["id"]])
            seqs.append(prompt + system.served_token_ids(r["text"]))
            first.append(len(prompt))
        nums, positions = check.served_numbers(args.seed, model, seqs, first,
                                               cell["traffic"]["check"])
        compared.update(check.beside(nums, check.limits_for(cell["config"]["name"])))
        info.append(f"info sampled requests={len(picked)} positions={positions} "
                    f"longest={len(seqs[0])} reference_s={time.monotonic() - t_ref:.2f} "
                    f"differ_share={nums['differ_share']:.6g} (positions where the served "
                    "token is not the reference's best; no limit)")
    compared["sampled_requests"] = {"value": len(picked), "limit_at_least": 1}
    compared["miscounted_requests"] = {"value": miscounted, "limit": 0}
    for what, bad in guards:
        compared[what.replace(" ", "_")] = {"value": len(bad), "limit": 0}
        if bad:
            info.append(f"info {what}: {bad}")
    compared["backend_compiles_inside_the_window"] = {"value": window_compiles, "limit": 0}
    return all(map(check.holds, compared.values())), compared, info


def run_cell(args):
    """One run; the result object, or an exit code when the chips are not there."""
    cell = bench_config.load_cell(args.workload, rehearse=args.rehearse)
    for item in args.engine:
        key, _, value = item.partition("=")
        try:
            cell["config"]["engine"][key] = json.loads(value)    # 256, true, null
        except ValueError:
            cell["config"]["engine"][key] = value                # w8a8, int8
    if args.rate_rps is not None:
        cell["params"]["rate_rps"] = args.rate_rps
    window_s = float(args.seconds)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if args.rehearse:
        if devs[0].platform != "cpu":
            note("bench: --rehearse needs JAX_PLATFORMS=cpu")
            return 2
    elif devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        note(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX reports "
             f"{len(devs)} x {devs[0].platform}. The benchmark does not run elsewhere.")
        return 2

    from bench import peaks, traffic

    system = bench_config.load_module("systems", cell["config"]["model"].get("system", "engine"))

    if not args.rehearse:
        peaks.for_kind(device["kind"])      # an unknown chip is an error, before any work
    out_dir = ROOT / "chiprun_out" / "bench" / cell["name"] / str(args.seed)
    out_dir.mkdir(parents=True, exist_ok=True)
    backend_compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: backend_compiles.append(time.monotonic())
        if name == "/jax/core/compile/backend_compile_duration" else None)

    # what can hold the interpreter inside the window: every timed JAX event
    # (tracing, lowering, a cache read) and every garbage collection, with its instant
    host_events: list[tuple[float, str, float]] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: host_events.append((time.monotonic(), name, secs)))
    gc_began: dict[int, float] = {}

    def on_gc(phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            gc_began[info["generation"]] = now
        else:
            began = gc_began.pop(info["generation"], now)
            host_events.append((began, f"gc.gen{info['generation']}", now - began))

    gc.callbacks.append(on_gc)

    model, tr = cell["config"]["model"], cell["traffic"]
    served = system.boot(cell["config"], args.seed, cell["chips"])
    client = None
    try:
        say(f"boot_s={served.boot_s:.2f} weights_s={served.weights_s:.2f} "
            f"platform={device['platform']} kind={device['kind']} count={device['count']}")
        overhead = len(system.chat_prompt_ids(served.tokenizer, "x")) - 1
        plan = traffic.plan(tr, cell["params"], args.seed, window_s, overhead)
        plan.update(url=served.url, model=model["name"], temperature=tr["temperature"],
                    traffic=cell["entry"]["traffic"],
                    warm=[{"id": f"w{p}", "phase": "warm", "due_s": None, "client": None,
                           "prompt_tokens": p, "max_tokens": 4,
                           "content": "w" * (p - overhead)}
                          for p in tr["warm_prompt_tokens"]])
        (out_dir / "plan.json").write_text(json.dumps(plan))
        client = subprocess.Popen(
            [sys.executable, str(ROOT / "bench" / "client.py"),
             str(out_dir / "plan.json"), str(out_dir / "records.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=str(ROOT))
        ready = client.stdout.readline().strip()     # the warm-up requests are done
        if not ready.startswith("ready"):
            raise RuntimeError(f"load generator failed to start: {ready!r}")
        warm_failed = json.loads(ready[5:] or "[]")
        if warm_failed:
            raise RuntimeError(f"warm-up requests failed: {warm_failed}")
        warm = served.compiled()
        t0 = time.monotonic() + plan["lead_in_s"] + 0.25
        client.stdin.write(f"{t0!r}\n")
        client.stdin.flush()
        say(f"warm executables={sorted(warm)} "
            f"compile_s={sum(e['compile_wall_s'] for e in warm.values()):.2f}")
        setup_s = t0 - T_START

        sleep_until(t0)
        before = scrape(served.url)
        trace_dir = None
        if args.trace:
            trace_dir = out_dir / "trace"
            sleep_until(t0 + max(window_s / 2 - TRACE_SECONDS / 2, 0.0))
            t_trace = time.monotonic()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the program's own spans only: cheaper
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            sleep_until(min(t_trace + TRACE_SECONDS, t0 + window_s))
            jax.profiler.stop_trace()
        sleep_until(t0 + window_s)
        after = scrape(served.url)
        gc.callbacks.remove(on_gc)
        held = [(round(t - t0, 2), name, round(secs, 4)) for t, name, secs in host_events
                if t0 <= t <= t0 + window_s and secs >= 0.005]
        say(f"window host events >= 5 ms: n={len(held)} total_s={sum(h[2] for h in held):.3f} "
            f"{held[:40]}")
        window_compiles = sum(1 for t in backend_compiles if t0 <= t <= t0 + window_s)
        compiled = served.compiled()
        compiled_in_window = sorted(set(compiled) - set(warm))
        no_mosaic = [] if args.rehearse else sorted(
            lab for lab, e in compiled.items()
            if lab.startswith("prefill[") and e["mosaic_calls"] == 0)
        client.stdin.close()
        client.wait(timeout=plan["drain_s"] + 60)
        if client.returncode != 0:
            raise RuntimeError(f"load generator exited with {client.returncode}")
        memory = devs[0].memory_stats() or {}
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
        engine_cfg = {"max_slots": served.slots, "max_seq_len": served.context}
        tokenizer = served.tokenizer
    finally:
        if client is not None and client.poll() is None:
            client.kill()
            client.wait()
        served.stop()
    del served
    gc.collect()

    run = json.loads((out_dir / "records.json").read_text())
    records, run_end_s = run["records"], run["ended_s"]
    counters = {k: after[k] - before.get(k, 0.0) for k in after}

    # -- correct: the served tokens against the plain reference, after the engine is freed
    correct, compared, info = decide_correct(
        args, cell, records, plan, tokenizer,
        guards=(("compiled inside the window", compiled_in_window),
                ("prefill executables without a Mosaic call", no_mosaic)),
        window_compiles=window_compiles)
    for line in info:
        say(line)
    in_window = metrics.due_in_window(records, window_s)

    # -- metrics
    trace = None
    if trace_dir is not None:
        from bench import trace_reduce

        trace = trace_reduce.reduce_dir(trace_dir)
        (out_dir / "trace_reduced.json").write_text(json.dumps(trace))
    ctx = Context(cell, records, window_s, run_end_s, setup_s, counters,
                  {**memory, "engine": engine_cfg, "model": model}, trace, device["kind"])
    out_metrics = collect(cell["per_layer"] if args.trace else cell["end_to_end"], ctx)
    attempted = len(in_window)
    n_failed = sum(1 for r in in_window if metrics.failed(r))
    late = metrics.lateness_ms(records, window_s)
    say(f"window requests due={attempted} failed={n_failed} completed="
        f"{sum(1 for r in in_window if metrics.completed(r))} tokens_in_window="
        f"{metrics.tokens_in_window(records, window_s)} gen_late_p95_ms="
        f"{metrics.percentile(late, 95.0) if late else float('nan'):.3f} setup_s={setup_s:.2f}")
    silent = metrics.token_silences(records, window_s)
    say(f"window token silences >= 0.1 s: n={len(silent)} total_s={sum(silent):.3f} "
        f"longest_s={max(silent, default=0.0):.3f}")
    tt = metrics.ttfts_ms(records, window_s, run_end_s)
    if tt and ctx.gaps_ms:
        say(f"window ttft_ms p50={metrics.percentile(tt, 50):.1f} p95={metrics.percentile(tt, 95):.1f} "
            f"itl_ms p50={metrics.percentile(ctx.gaps_ms, 50):.2f} p95={metrics.percentile(ctx.gaps_ms, 95):.2f} "
            f"p99={metrics.percentile(ctx.gaps_ms, 99):.2f} out_tokens_per_s="
            f"{metrics.tokens_in_window(records, window_s) / window_s:.1f} "
            f"run_end_s={run_end_s:.2f} wall_s={time.monotonic() - T_START:.1f}")
    device["memory_peak_bytes"] = int(memory_peak)
    result = {"correct": bool(correct), "attempted": attempted, "failed": n_failed,
              "metrics": out_metrics, "device": device}
    if trace is not None:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    # every number compared beside its limit: last in the line, and (main) last on stderr
    result["checks"] = compared
    (out_dir / f"result.trace{args.trace}.json").write_text(json.dumps(result))
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    result = run_cell(args)
    if isinstance(result, int):
        return result
    if args.rehearse:
        # a CPU run is a rehearsal of the harness: it proves nothing about the
        # chip, so it says so where the driver would read a result
        say(f"rehearsal (CPU, not device numbers): correct={result['correct']} "
            + json.dumps(result["metrics"]))
        result.update(correct=False, metrics={})
    say(json.dumps(result))
    from bench import check

    for line in check.lines_of(result["checks"]):
        note(line)
    note(f"correct={result['correct']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
