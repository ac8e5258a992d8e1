"""kvmini-lint: per-rule fixture assertions + the live-codebase baseline pin.

JAX-free by construction (the linter is stdlib-ast only), so this suite
runs in the harness-only lane. Each KVM0xx rule has a bad/ fixture that
must produce EXACTLY the expected diagnostics and a good/ fixture (same
shape, invariant respected or legitimately suppressed) that must lint
clean — including the ISSUE's seeded mutations: an unpublished lockstep
mutation (KVM021), a stats key missing from /metrics (KVM031),
time.time() inside a jitted fn (KVM013), the KVM05x seeded races
(bare cross-thread counter increment, lock-order cycle, unbounded
Event.wait/join), and the KVM06x/07x seeded numerics/lifecycle bugs
(bf16 x f32-scale upcast, dequant dropping the zero-point, the
ops/quant.py sub-byte bitcast unpack, donated buffer read after
dispatch, double-free of a KV block id), and the KVM10x/11x protocol
and contract mutations (a published decision with no replay arm, an
ungated host-only field read, an unnegotiated handoff version, a
degrade-flag re-arm, fabricated zeros in exported surfaces, event
taxonomy drift, an HTTP surface the mock/docs don't mirror).

The pin test runs the real linter over the real package against the
committed lint-baseline.json: no new findings, no stale entries, no
stale suppressions — and inside the <10s budget CI's lint-invariants
target relies on.
"""

from __future__ import annotations

import json
import time
from collections import Counter
from pathlib import Path

import pytest

from kserve_vllm_mini_tpu.lint import baseline as baseline_mod
from kserve_vllm_mini_tpu.lint.__main__ import main as lint_main
from kserve_vllm_mini_tpu.lint.diagnostics import RULES, Diagnostic
from kserve_vllm_mini_tpu.lint.runner import run_lint

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "lint_fixtures"
PACKAGE = REPO / "kserve_vllm_mini_tpu"


def lint_fixture(rule: str, case: str) -> list[Diagnostic]:
    root = FIXTURES / rule / case
    docs = root / "docs"
    result = run_lint(
        [root],
        doc_paths=[docs] if docs.is_dir() else None,
        root=REPO,
    )
    assert not result.parse_errors, result.parse_errors
    return result.diagnostics


def codes(diags: list[Diagnostic]) -> Counter:
    return Counter(d.code for d in diags)


# -- per-rule fixtures: (rule dir, expected bad-case code counts) -----------
CASES = [
    ("kvm001", {"KVM001": 1}),
    ("kvm011", {"KVM011": 1}),
    ("kvm012", {"KVM012": 1}),
    ("kvm013", {"KVM013": 2}),  # ISSUE seeded mutation: time.time() under jit
    #                             (+ the from-imported-clock spelling)
    ("kvm014", {"KVM014": 1}),
    ("kvm015", {"KVM015": 3}),  # traced code, dispatch path, inline lambda
    ("kvm021", {"KVM021": 2}),  # ISSUE seeded mutation: unpublished admit;
    #                             publish elsewhere must not excuse a block
    ("kvm022", {"KVM022": 2}),  # set iteration + wall-clock branch
    ("kvm031", {"KVM031": 1}),  # ISSUE seeded mutation: stats key not exported
    ("kvm032", {"KVM032": 3}),  # consumed-, documented-, and emitted-drift
    ("kvm033", {"KVM033": 1}),
    ("kvm041", {"KVM041": 3}),  # silent except-fallback + unflagged
    #                             truncation + ISSUE-10 seeded swallowed 429
    ("kvm051", {"KVM051": 1}),  # ISSUE seeded race: bare cross-thread counter
    ("kvm052", {"KVM052": 1}),  # locked read here, bare write there
    ("kvm053", {"KVM053": 1}),  # ISSUE seeded race: lock-order cycle
    ("kvm054", {"KVM054": 2}),  # ISSUE seeded race: unbounded wait + join
    ("kvm055", {"KVM055": 1}),  # raw live deque handed across the boundary
    ("kvm061", {"KVM061": 1}),  # ISSUE seeded bug: bf16 x f32-scale upcast
    ("kvm062", {"KVM062": 1}),  # ISSUE seeded bug: dequant drops zero-point
    ("kvm063", {"KVM063": 2}),  # ISSUE seeded bug: the ops/quant.py sub-byte
    #                             bitcast unpack (+ a materialized int4 leaf)
    ("kvm064", {"KVM064": 2}),  # int8 dot() and `@` without accum dtype
    ("kvm065", {"KVM065": 1}),  # softmax over bf16
    ("kvm071", {"KVM071": 1}),  # ISSUE seeded bug: donated buffer read after
    #                             dispatch
    ("kvm072", {"KVM072": 1}),  # KV cache threaded through undonated
    ("kvm073", {"KVM073": 2}),  # ISSUE seeded bug: double-free of a KV block
    #                             id (+ a table write after free)
    ("kvm074", {"KVM074": 1}),  # retained-LRU claim without unpin
    ("kvm081", {"KVM081": 1}),  # ISSUE seeded bug: psum over an axis the
    #                             enclosing shard_map's mesh never binds
    ("kvm082", {"KVM082": 3}),  # ISSUE seeded bug: wrong-arity PartitionSpec
    #                             (+ axis typo + in_specs/param mismatch)
    ("kvm083", {"KVM083": 1}),  # ISSUE seeded bug: device_put in the decode
    #                             dispatch path (per-step hidden reshard)
    ("kvm084", {"KVM084": 1}),  # donated cache resharded across the
    #                             shard_map boundary (silent copy)
    ("kvm091", {"KVM091": 1}),  # ISSUE seeded bug: slot acquire leaking
    #                             through an except branch
    ("kvm092", {"KVM092": 1}),  # ISSUE seeded bug: double release on the
    #                             drain path (abort already released)
    ("kvm093", {"KVM093": 1}),  # finally re-raises past the pending release
    ("kvm101", {"KVM101": 2}),  # ISSUE seeded mutation: published "handoff"
    #                             with no replay arm + dead "dispatch" arm
    ("kvm102", {"KVM102": 1}),  # ISSUE seeded mutation: ungated host-only
    #                             deadline_s read on the replay path
    ("kvm103", {"KVM103": 2}),  # ISSUE seeded mutation: handoff stamped with
    #                             an unnegotiated constant + a raw int
    ("kvm104", {"KVM104": 2}),  # ISSUE seeded mutation: False re-arm outside
    #                             reset + sticky flag with no entry edge
    ("kvm111", {"KVM111": 3}),  # ISSUE seeded mutation: fabricated zeros in
    #                             /metrics (.get default, or-0) + results key
    ("kvm112", {"KVM112": 4}),  # ISSUE seeded mutation: emit/consume drift
    #                             vs EVENT_TYPES + an undocumented member
    ("kvm113", {"KVM113": 4}),  # ISSUE seeded mutation: mockless client
    #                             path, phantom mock route, undocumented
    #                             endpoint, shed response sans Retry-After
    ("kvm121", {"KVM121": 2}),  # ISSUE seeded bug: time.sleep + sync HTTP
    #                             in a helper reachable from a route handler
    ("kvm122", {"KVM122": 2}),  # bare create_task + ensure_future spawns
    ("kvm123", {"KVM123": 1}),  # ISSUE seeded race: scrape thread and
    #                             handler both mutate loop state, unrouted
    ("kvm124", {"KVM124": 2}),  # ISSUE seeded bug: single-statement and
    #                             bound-local RMW straddling an await
    ("kvm131", {"KVM131": 1}),  # ISSUE seeded drift: env knob in no table
    #                             and no docs page
    ("kvm132", {"KVM132": 1}),  # knob-table entry with no read site
    ("kvm133", {"KVM133": 2}),  # unreachable config field + flag that no
    #                             docs page mentions
    ("kvm134", {"KVM134": 1}),  # argparse default= vs dataclass default
]


@pytest.mark.parametrize("rule,expected", CASES, ids=[c[0] for c in CASES])
def test_bad_fixture_produces_exactly_the_expected_diagnostics(rule, expected):
    assert dict(codes(lint_fixture(rule, "bad"))) == expected


@pytest.mark.parametrize("rule", [c[0] for c in CASES], ids=[c[0] for c in CASES])
def test_good_fixture_lints_clean(rule):
    diags = lint_fixture(rule, "good")
    assert diags == [], [d.render() for d in diags]


def test_partial_scan_never_calls_protocol_suppressions_stale():
    """The KVM10x/11x families stand down on subset scans (the missing
    replay arm may live in an unscanned module) — so must the KVM001
    staleness check for their tokens: a single-file scan of the publish
    side cannot see the follower that makes its protocol-ok earn its
    keep, and must not demand the annotation be deleted."""
    publisher = FIXTURES / "kvm101" / "good" / "runtime" / "engine.py"
    result = run_lint([publisher], root=REPO)
    assert not result.parse_errors
    assert result.diagnostics == [], [
        d.render() for d in result.diagnostics
    ]


def test_partial_scan_never_calls_async_or_config_suppressions_stale():
    """Same stand-down contract for the KVM12x/13x tokens: a single-file
    scan of kvm121/good's handlers.py cannot see the registration (in
    app.py) that makes its `async-ok` earn its keep, and KVM131 only
    runs on full scans at all — neither token may be called stale on a
    subset scan."""
    handlers = FIXTURES / "kvm121" / "good" / "handlers.py"
    result = run_lint([handlers], root=REPO)
    assert not result.parse_errors
    assert result.diagnostics == [], [d.render() for d in result.diagnostics]
    scraper = FIXTURES / "kvm131" / "good" / "scraper.py"
    result = run_lint([scraper], root=REPO)
    assert not result.parse_errors
    assert result.diagnostics == [], [d.render() for d in result.diagnostics]


def test_every_rule_code_has_a_fixture():
    covered = {c.upper() for c, _ in CASES}
    assert covered == set(RULES), "fixture coverage must track the rule table"


# -- baseline ratchet mechanics ---------------------------------------------

def _diag(path="a.py", code="KVM013", ctx="f") -> Diagnostic:
    return Diagnostic(path, 1, code, "msg", context=ctx)


def test_baseline_grandfathers_exact_matches(tmp_path):
    bl = tmp_path / "bl.json"
    baseline_mod.save(bl, [_diag(), _diag(ctx="g")])
    diff = baseline_mod.diff([_diag(), _diag(ctx="g")], baseline_mod.load(bl))
    assert diff.clean and diff.suppressed == 2 and not diff.new


def test_baseline_flags_new_findings(tmp_path):
    bl = tmp_path / "bl.json"
    baseline_mod.save(bl, [_diag()])
    diff = baseline_mod.diff([_diag(), _diag(ctx="brand_new")],
                             baseline_mod.load(bl))
    assert not diff.clean
    assert [d.context for d in diff.new] == ["brand_new"]


def test_baseline_grandfathers_budget_when_count_grows(tmp_path):
    # a third same-key finding must not repaint the recorded two as new
    bl = tmp_path / "bl.json"
    baseline_mod.save(bl, [_diag(), _diag()])
    three = [Diagnostic("a.py", ln, "KVM013", "msg", context="f")
             for ln in (1, 5, 9)]
    diff = baseline_mod.diff(three, baseline_mod.load(bl))
    assert diff.suppressed == 2
    assert [d.line for d in diff.new] == [9]


def test_out_of_root_paths_lint_without_crashing(tmp_path):
    # paths outside the lint root keep their absolute identity (no
    # ValueError from relative_to) and still produce diagnostics
    src = tmp_path / "probe.py"
    src.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n")
    result = run_lint([tmp_path], root=REPO)
    assert not result.parse_errors
    assert [d.code for d in result.diagnostics] == ["KVM015"]


def test_one_getter_over_a_module_of_builders_is_a_jitted_source(tmp_path):
    """The engine's idiom since runtime/steps.py: a builder in another
    module returns ``jax.jit(step, donate_argnums=...)``, one getter binds
    the result to a local, files it in a dict and returns it. The function
    that calls what the getter hands out is a jit-dispatch hot path, and
    the builder's donations reach its call."""
    (tmp_path / "steps.py").write_text(
        "import jax\n\n"
        "def build_decode(n):\n"
        "    def step(params, cache):\n"
        "        return cache\n"
        "    return jax.jit(step, donate_argnums=(1,))\n")
    (tmp_path / "eng.py").write_text(
        "import steps\n\n"
        "class Engine:\n"
        "    def _step(self, kind, n):\n"
        "        fn = self._steps.get((kind, n))\n"
        "        if fn is None:\n"
        "            fn = steps.build_decode(n)\n"
        "            fn = self._instrument(fn, kind)\n"
        "            self._steps[kind, n] = fn\n"
        "        return fn\n\n"
        "    def sweep(self, cache):\n"
        "        decode = self._step('decode', 1)\n"
        "        out = decode(self.params, cache)\n"
        "        return out.item(), cache\n")
    result = run_lint([tmp_path], root=tmp_path)
    assert not result.parse_errors
    assert sorted((d.code, d.context) for d in result.diagnostics) == [
        ("KVM015", "Engine.sweep"), ("KVM071", "Engine.sweep")]


def test_baseline_flags_stale_entries_as_ratchet(tmp_path):
    bl = tmp_path / "bl.json"
    baseline_mod.save(bl, [_diag(), _diag(ctx="fixed_since")])
    diff = baseline_mod.diff([_diag()], baseline_mod.load(bl))
    assert not diff.clean
    assert diff.stale == ["a.py::KVM013::fixed_since"]


# -- CLI contract ------------------------------------------------------------

def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = str(FIXTURES / "kvm013" / "bad")
    assert lint_main([bad, "--no-baseline", "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert {f["code"] for f in doc["findings"]} == {"KVM013"}

    bl = tmp_path / "bl.json"
    assert lint_main([bad, "--write-baseline", "--baseline", str(bl)]) == 0
    assert lint_main([bad, "--baseline", str(bl)]) == 0  # grandfathered
    good = str(FIXTURES / "kvm013" / "good")
    assert lint_main([good, "--baseline", str(bl)]) == 1  # stale entry ratchets
    assert lint_main([str(tmp_path / "nope")]) == 2


def test_single_file_scan_skips_cross_surface_drift():
    # linting one changed file must not fail on metrics other (unscanned)
    # emitter modules provide — docs drift is a directory-scan check
    result = run_lint(
        [PACKAGE / "runtime" / "server.py"],
        doc_paths=[REPO / "docs", REPO / "dashboards"],
        root=REPO,
    )
    assert [d.render() for d in result.diagnostics if d.code == "KVM032"] == []


def test_family_filter_selects_checkers(capsys):
    bad13 = str(FIXTURES / "kvm013" / "bad")
    # the KVM01 findings vanish under a KVM05-only scan...
    assert lint_main([bad13, "--no-baseline", "--family", "KVM05"]) == 0
    capsys.readouterr()
    # ...and are still there when their own family is selected
    assert lint_main([bad13, "--no-baseline", "--family", "KVM01"]) == 1


def test_family_filter_spares_foreign_suppressions():
    # kvm001/good holds a USED `static-shape` suppression (a KVM01 token);
    # a KVM05-only run never fires KVM011, but must not call it stale
    good = str(FIXTURES / "kvm001" / "good")
    assert lint_main([good, "--no-baseline", "--family", "KVM05"]) == 0


def test_family_filter_full_code_and_validation(capsys):
    bad51 = str(FIXTURES / "kvm051" / "bad")
    assert lint_main([bad51, "--no-baseline", "--family", "KVM051"]) == 1
    capsys.readouterr()
    assert lint_main([bad51, "--no-baseline", "--family", "KVM99"]) == 2
    # a family-sliced baseline would silently drop every other family
    assert lint_main([bad51, "--family", "KVM05", "--write-baseline"]) == 2


def test_family_filter_rejects_unselectable_kvm001(capsys):
    # KVM001 rides along with whatever rules run; selecting it alone
    # would run zero checkers and report a green no-op — usage error
    bad51 = str(FIXTURES / "kvm051" / "bad")
    assert lint_main([bad51, "--no-baseline", "--family", "KVM001"]) == 2


def test_lockish_name_is_word_bounded(tmp_path):
    # `self._block` (a KV pool, not a lock) must NOT count as a guard:
    # wrapping accesses in a non-lock context manager neither invents a
    # KVM052 nor masks the real unguarded cross-thread mutation
    (tmp_path / "pool.py").write_text(
        "import threading\n\n\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._block = object()\n"
        "        self.used = 0\n\n"
        "    def _loop(self):\n"
        "        while True:\n"
        "            with self._block:\n"
        "                self.used += 1\n\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._loop, daemon=True).start()\n\n"
        "    def read(self):\n"
        "        with self._block:\n"
        "            return self.used\n"
    )
    result = run_lint([tmp_path], root=REPO)
    assert [d.code for d in result.diagnostics] == ["KVM051"]


def test_family_filter_full_code_drops_sibling_findings(capsys):
    # `--family KVM051` runs the whole KVM05 checker (family granularity)
    # but must report ONLY KVM051 — a sibling KVM053 in the scanned tree
    # stays out of the output, as the help text promises
    bad51 = str(FIXTURES / "kvm051" / "bad")
    bad53 = str(FIXTURES / "kvm053" / "bad")
    rc = lint_main([bad51, bad53, "--no-baseline", "--family", "KVM051",
                    "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["code"] for f in doc["findings"]} == {"KVM051"}


def test_timing_report(tmp_path, capsys):
    bad51 = str(FIXTURES / "kvm051" / "bad")
    rc = lint_main([bad51, "--no-baseline", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {"facts", "concurrency"} <= set(doc["timings"])
    # timings keys are ORDERED: "facts" first, then family-code order —
    # diffing two lint-timing.json artifacts line-by-line must attribute
    # a regression to a checker, not to dict-insertion happenstance
    assert list(doc["timings"]) == [
        "facts", "jit_purity", "lockstep", "metrics_drift", "workload",
        "concurrency", "dtype_flow", "buffer_lifecycle", "mesh_flow",
        "resource_paths", "protocol_flow", "contract_flow", "async_flow",
        "config_flow",
    ]
    rc = lint_main([bad51, "--no-baseline", "--timing"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "kvmini-lint timing: " in out and "concurrency" in out
    # --timing-out: the CI artifact comes from the SAME gating run
    report = tmp_path / "lint-timing.json"
    assert lint_main([bad51, "--no-baseline",
                      "--timing-out", str(report)]) == 1
    doc = json.loads(report.read_text())
    assert "concurrency" in doc["timings"] and doc["findings"] == 1
    # serial-vs-parallel wall from ONE artifact: elapsed_s is the wall,
    # serial_equivalent_s the sum of per-family times a serial run pays
    assert doc["serial_equivalent_s"] == pytest.approx(
        sum(doc["timings"].values()), abs=0.01)
    # per-family counts ride along: ms alone can't tell "fast because
    # clean" from "fast because broken"
    counts = doc["findings_by_checker"]
    assert counts["concurrency"] == 1
    # every checker that ran reports an explicit 0 (absence = didn't run)
    assert counts["mesh_flow"] == 0 and counts["resource_paths"] == 0


def test_parallel_and_serial_runs_are_byte_identical(tmp_path):
    """--jobs is a wall-clock knob, never a semantic one: the findings
    list and the rendered SARIF must match byte-for-byte between an
    explicit serial run and a 4-way pool over the same tree."""
    from kserve_vllm_mini_tpu.lint import sarif as sarif_mod

    scope = [FIXTURES / "kvm051" / "bad", FIXTURES / "kvm121" / "bad",
             FIXTURES / "kvm131" / "bad", FIXTURES / "kvm013" / "bad"]
    serial = run_lint(scope, root=REPO, jobs=1)
    pooled = run_lint(scope, root=REPO, jobs=4)
    assert [d.render() for d in serial.diagnostics] \
        == [d.render() for d in pooled.diagnostics]
    assert serial.diagnostics, "determinism check needs a non-empty scan"
    assert json.dumps(sarif_mod.render(serial.diagnostics)) \
        == json.dumps(sarif_mod.render(pooled.diagnostics))


def test_sarif_output(tmp_path):
    """--sarif writes a 2.1.0 doc: severity from the rule family, repo-
    relative URIs, the full rule table, suppressed findings omitted."""
    sarif = tmp_path / "out.sarif"
    assert lint_main([str(FIXTURES / "kvm063" / "bad"), "--no-baseline",
                      "--sarif", str(sarif)]) == 1
    doc = json.loads(sarif.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "kvmini-lint"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == set(RULES)
    assert [r["ruleId"] for r in run["results"]] == ["KVM063", "KVM063"]
    # numerics are correctness-of-served-bytes: family maps to error
    assert {r["level"] for r in run["results"]} == {"error"}
    loc = run["results"][0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].startswith("tests/lint_fixtures/")
    assert loc["region"]["startLine"] > 0

    # a good tree's suppressed findings never reach the document
    assert lint_main([str(FIXTURES / "kvm061" / "good"), "--no-baseline",
                      "--sarif", str(sarif)]) == 0
    doc = json.loads(sarif.read_text())
    assert doc["runs"][0]["results"] == []


def test_sarif_family_severity_mapping():
    from kserve_vllm_mini_tpu.lint.sarif import level_for
    assert level_for("KVM001") == "note"
    assert level_for("KVM013") == "warning"   # jit purity: convention
    assert level_for("KVM032") == "warning"   # drift: convention
    assert level_for("KVM021") == "error"     # lockstep: served bytes
    assert level_for("KVM051") == "error"     # thread safety
    assert level_for("KVM061") == "error"     # numerics
    assert level_for("KVM073") == "error"     # buffer lifecycle


def test_write_baseline_refuses_parse_errors(tmp_path, capsys):
    (tmp_path / "broken.py").write_text("def f(:\n")
    bl = tmp_path / "bl.json"
    rc = lint_main([str(tmp_path), "--write-baseline", "--baseline", str(bl)])
    assert rc == 2 and not bl.exists()
    assert "parse error" in capsys.readouterr().err


# -- --changed mode: the fast pre-commit subset scan -------------------------

def _git(tmp_path, *args):
    import subprocess

    subprocess.run(["git", *args], cwd=tmp_path, check=True,
                   capture_output=True)


def test_changed_mode_scans_changed_files_plus_consumers(tmp_path,
                                                         monkeypatch,
                                                         capsys):
    """--changed REF lints only the git-diff files AND their importers
    (reverse deps through the fact index); untouched non-consumers stay
    out of the scan even when they carry findings of their own."""
    (tmp_path / "base.py").write_text(
        "import jax\n\n\n@jax.jit\ndef f(x):\n    return x\n")
    (tmp_path / "consumer.py").write_text(
        "import time\n\nimport jax\n\nfrom base import f\n\n\n"
        "@jax.jit\ndef g(x):\n    return f(x) * time.time()\n")
    (tmp_path / "other.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef h(x):\n    return x * time.time()\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    # mutate ONLY base.py (introduce its own finding too)
    (tmp_path / "base.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef f(x):\n    return x * time.time()\n")
    monkeypatch.chdir(tmp_path)
    rc = lint_main([".", "--changed", "HEAD", "--no-baseline",
                    "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    paths = {f["path"] for f in doc["findings"]}
    # base changed; consumer imports base (re-linted); other is untouched
    assert any(p.endswith("base.py") for p in paths)
    assert any(p.endswith("consumer.py") for p in paths)
    assert not any(p.endswith("other.py") for p in paths)


def test_changed_mode_nothing_changed_and_bad_ref(tmp_path, monkeypatch,
                                                  capsys):
    (tmp_path / "mod.py").write_text("x = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    monkeypatch.chdir(tmp_path)
    assert lint_main([".", "--changed", "HEAD"]) == 0
    assert "nothing to lint" in capsys.readouterr().out
    # an unresolvable ref fails LOUDLY (rc 2), never a silently-green scan
    assert lint_main([".", "--changed", "no-such-ref"]) == 2
    # the baseline must come from a full scan, never a subset
    assert lint_main([".", "--changed", "HEAD", "--write-baseline"]) == 2


def test_changed_mode_includes_untracked_files(tmp_path, monkeypatch,
                                               capsys):
    """A brand-new (untracked) module never shows in `git diff`, but it
    must still be scanned — 'nothing to lint' on a new file would be the
    silently-green scan docs/LINTING.md promises never happens."""
    (tmp_path / "old.py").write_text("x = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    (tmp_path / "brandnew.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef h(x):\n    return x * time.time()\n")
    monkeypatch.chdir(tmp_path)
    rc = lint_main([".", "--changed", "HEAD", "--no-baseline",
                    "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(f["path"].endswith("brandnew.py") for f in doc["findings"])


def test_changed_mode_resolves_git_paths_from_a_subdirectory(tmp_path,
                                                             monkeypatch,
                                                             capsys):
    """git prints paths relative to the repo TOPLEVEL; running the scan
    from a subdirectory must still intersect them with the scope."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text("import jax\n\n\n@jax.jit\ndef f(x):\n    return x\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    (pkg / "mod.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef f(x):\n    return x * time.time()\n")
    # ...and an UNTRACKED file: ls-files prints cwd-relative paths
    # (unlike diff's toplevel-relative ones) — --full-name must align
    # them or the combination untracked+subdir is silently missed
    (pkg / "fresh.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef g(x):\n    return x * time.time()\n")
    monkeypatch.chdir(pkg)
    rc = lint_main([".", "--changed", "HEAD", "--no-baseline",
                    "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(f["path"].endswith("mod.py") for f in doc["findings"])
    assert any(f["path"].endswith("fresh.py") for f in doc["findings"])


def test_changed_mode_skips_deleted_and_renamed_files(tmp_path, monkeypatch,
                                                      capsys):
    """A deleted (or renamed-away) tracked file shows in `git diff
    --name-only` but no longer exists — the subset scan must skip it
    with a note instead of handing run_lint a missing path, and still
    lint the files that DO exist."""
    (tmp_path / "doomed.py").write_text("x = 1\n")
    (tmp_path / "kept.py").write_text(
        "import jax\n\n\n@jax.jit\ndef f(x):\n    return x\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    (tmp_path / "doomed.py").unlink()
    (tmp_path / "kept.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef f(x):\n    return x * time.time()\n")
    monkeypatch.chdir(tmp_path)
    rc = lint_main([".", "--changed", "HEAD", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "skipping 1 deleted/renamed file(s): doomed.py" in out
    assert "KVM013" in out and "kept.py" in out

    # ONLY deletions in the diff: empty subset, clean exit, note intact
    (tmp_path / "kept.py").unlink()
    rc = lint_main([".", "--changed", "HEAD", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "skipping 2 deleted/renamed file(s): doomed.py, kept.py" in out
    assert "nothing to lint" in out


def test_partial_scan_never_invents_mesh_findings(tmp_path, monkeypatch,
                                                  capsys):
    """Subset-vs-full soundness for the absence-based mesh rules: helper
    runs a collective under wrapper.py's shard_map scope; a --changed
    scan touching only the helper cannot see the scope and must stand
    DOWN (no KVM081), not misread the helper as scope-free."""
    (tmp_path / "helper_mod.py").write_text(
        "import jax\n\n\n@jax.jit\ndef helper(x):\n"
        "    return jax.lax.psum(x, 'dp')\n")
    (tmp_path / "wrapper.py").write_text(
        "from functools import partial\n\n"
        "import jax\nfrom jax import shard_map\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n\n"
        "from helper_mod import helper\n\nAXES = ('dp', 'tp')\n\n\n"
        "def build(devices):\n"
        "    mesh = Mesh(devices, AXES)\n\n"
        "    @partial(shard_map, mesh=mesh, in_specs=(P('dp'),),\n"
        "             out_specs=P('dp'))\n"
        "    def run(x):\n"
        "        return helper(x)\n\n"
        "    return run\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    (tmp_path / "helper_mod.py").write_text(
        "import jax\n\n\n@jax.jit\ndef helper(x):\n"
        "    return jax.lax.psum(x, 'dp') + 0\n")
    monkeypatch.chdir(tmp_path)
    # full scan: scope resolves, axis bound — clean
    assert lint_main([".", "--no-baseline"]) == 0
    # subset scan (helper only — wrapper imports it, so it IS pulled in
    # as a consumer; the point stands via the single-file form too)
    assert lint_main([".", "--changed", "HEAD", "--no-baseline"]) == 0
    capsys.readouterr()
    # the single-file scan is the pure absence case: no scope in view
    assert lint_main([str(tmp_path / "helper_mod.py"),
                      "--no-baseline"]) == 0


def test_changed_mode_scopes_baseline_to_scanned_files(tmp_path,
                                                       monkeypatch):
    """A subset scan must not call an unscanned file's grandfathered
    finding stale — only the full scan ratchets the whole baseline."""
    (tmp_path / "legacy.py").write_text(
        "import time\n\nimport jax\n\n\n"
        "@jax.jit\ndef old(x):\n    return x * time.time()\n")
    (tmp_path / "fresh.py").write_text("import jax\n\n\ndef g(x):\n    return x\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "lint@test")
    _git(tmp_path, "config", "user.name", "lint test")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    monkeypatch.chdir(tmp_path)
    bl = tmp_path / "bl.json"
    assert lint_main([".", "--write-baseline", "--baseline", str(bl)]) == 0
    # touch ONLY fresh.py: legacy's grandfathered finding is out of scope
    (tmp_path / "fresh.py").write_text(
        "import jax\n\n\ndef g(x):\n    return x + 1\n")
    assert lint_main([".", "--changed", "HEAD", "--baseline", str(bl)]) == 0
    # the FULL scan still sees the whole baseline (nothing stale yet)
    assert lint_main([".", "--baseline", str(bl)]) == 0


# -- the live codebase stays pinned to the committed baseline ----------------

def test_live_codebase_matches_baseline_exactly():
    """No new findings, no stale baseline entries, no stale suppressions —
    and within the <10s budget `make lint-invariants` runs under."""
    t0 = time.perf_counter()
    result = run_lint(
        [PACKAGE],
        doc_paths=[REPO / "docs", REPO / "dashboards"],
        baseline_path=REPO / "lint-baseline.json",
        root=REPO,
    )
    elapsed = time.perf_counter() - t0
    assert not result.parse_errors, result.parse_errors
    assert result.baseline_diff is not None, "lint-baseline.json must exist"
    assert result.baseline_diff.new == [], [
        d.render() for d in result.baseline_diff.new
    ]
    assert result.baseline_diff.stale == [], (
        "fixed findings still in lint-baseline.json — regenerate with "
        "--write-baseline: " + ", ".join(result.baseline_diff.stale)
    )
    assert not [d for d in result.diagnostics if d.code == "KVM001"], (
        "stale `# kvmini:` suppressions in the live tree (dtype-ok/"
        "buffer-ok/mesh-ok/resource-ok/protocol-ok/contract-ok/async-ok/"
        "config-ok included — KVM001 tracks every token)"
    )
    # every family ran and reported its wall time — all FOURTEEN timing
    # entries, the `--timing` surface CI uploads to attribute speed drift
    assert {"facts", "jit_purity", "lockstep", "workload", "concurrency",
            "metrics_drift", "dtype_flow", "buffer_lifecycle",
            "mesh_flow", "resource_paths", "protocol_flow",
            "contract_flow", "async_flow", "config_flow"} \
        <= set(result.timings)
    # 10s: ~9s idle on this box with all FOURTEEN families after the
    # scope/walk memoization and the shared concurrency facts (serial
    # was ~16s before; the thread-pool engine only helps on multi-core
    # runners — this box has one CPU, so the pin covers the serial
    # path). lint-timing.json (CI artifact, with per-family finding
    # counts and serial_equivalent_s) still names the checker if one
    # of them regresses.
    assert elapsed < 10.0, f"kvmini-lint took {elapsed:.1f}s (budget 10s)"
