"""Lock discipline, both halves (SERVING.md rung 19).

Static: locklint's four rules against a fixture corpus of known true
positives and true negatives — each rule is also run DISABLED to prove
the fixture only passes because the rule exists — plus suppression
parsing, the JSON report schema, the CLI's exit-code contract, and the
gate itself: the real ``kvedge_tpu/`` package must produce zero
unsuppressed findings, and every suppression must carry a reason.

Dynamic: the DebugLock ownership assertions — unit semantics, the
Condition duck-typing seam, ``instrument_locked_methods`` — and a live
``PagedGenerationServer(debug_locks=True)`` serving tokens bit-identical
to the plain-lock server while refusing an unheld ``*_locked`` call.

All fixed-seed and fast: these run in the tier-1 gate (``-m lint``,
``tools/run_tests.py --lint``).
"""

import json
import pathlib
import subprocess
import sys
import threading

import pytest

from kvedge_tpu.analysis.locklint import (
    RULE_IDS,
    RULES,
    lint_file,
    lint_paths,
    lint_source,
    to_report,
)
from kvedge_tpu.runtime.debuglock import (
    DebugCondition,
    DebugLock,
    LockDisciplineError,
    assert_held,
    instrument_locked_methods,
    make_lock,
)

pytestmark = pytest.mark.lint

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = REPO / "kvedge_tpu"
FIXTURES = REPO / "tests" / "fixtures" / "locklint"


def unsuppressed(findings):
    return [f for f in findings if not f.suppressed]


def ids_of(findings):
    return {f.id for f in findings}


# ---- the gate: the real tree is clean ---------------------------------


def test_package_has_zero_unsuppressed_findings():
    findings = lint_paths([str(PACKAGE)])
    bad = unsuppressed(findings)
    assert not bad, "locklint findings on kvedge_tpu/:\n" + "\n".join(
        f.render() for f in bad
    )


def test_every_package_suppression_carries_a_reason():
    findings = lint_paths([str(PACKAGE)])
    sup = [f for f in findings if f.suppressed]
    # The tree's audited sites exist (the serving fair-handoff
    # zero-sleep at minimum) — an empty suppression list would mean
    # the analyzer stopped seeing them, not that the tree got cleaner.
    assert sup, "expected audited (suppressed) sites in the tree"
    assert all(f.suppress_reason for f in sup)
    srcs = {f.path for f in sup}
    assert any(p.endswith("models/serving.py") for p in srcs)


# ---- per-rule fixtures: TP, TN, and fails-when-disabled ----------------

_RULE_CASES = [
    ("L1", "l1_violations.py", "l1_clean.py",
     {"unlocked-call", "relock"}, 3),
    ("L2", "l2_violations.py", "l2_clean.py",
     {"sleep-under-lock", "device-sync-under-lock", "io-under-lock",
      "foreign-wait-under-lock"}, 8),
    ("L3", "l3_violations.py", "l3_clean.py",
     {"wait-not-in-loop", "notify-without-lock"}, 3),
    ("L4", "l4_violations.py", "l4_clean.py",
     {"unguarded-write"}, 2),
]


@pytest.mark.parametrize(
    "rule,tp,tn,expect_ids,expect_n",
    _RULE_CASES, ids=[c[0] for c in _RULE_CASES],
)
def test_rule_true_positives(rule, tp, tn, expect_ids, expect_n):
    findings = lint_file(FIXTURES / tp)
    mine = [f for f in findings if f.rule == rule]
    assert len(mine) == expect_n, [f.render() for f in findings]
    assert ids_of(mine) == expect_ids
    # The violations file must not trip OTHER rules — each fixture
    # isolates one rule, so a cross-rule finding is fixture rot.
    assert all(f.rule == rule for f in findings)


@pytest.mark.parametrize(
    "rule,tp,tn,expect_ids,expect_n",
    _RULE_CASES, ids=[c[0] for c in _RULE_CASES],
)
def test_rule_true_negatives(rule, tp, tn, expect_ids, expect_n):
    findings = lint_file(FIXTURES / tn)
    assert findings == [], [f.render() for f in findings]


@pytest.mark.parametrize(
    "rule,tp,tn,expect_ids,expect_n",
    _RULE_CASES, ids=[c[0] for c in _RULE_CASES],
)
def test_rule_disabled_silences_its_findings(rule, tp, tn, expect_ids,
                                             expect_n):
    """Each rule's fixture MUST go quiet when only that rule is off —
    i.e. the detection is attributable to the rule, not a side effect."""
    without = tuple(r for r in RULES if r != rule)
    remaining = lint_file(FIXTURES / tp, rules=without)
    assert all(f.rule != rule for f in remaining)
    assert len(remaining) < expect_n or expect_n == 0
    only = lint_file(FIXTURES / tp, rules=(rule,))
    assert len([f for f in only if f.rule == rule]) == expect_n


def test_rule_ids_registry_matches_emissions():
    """Every id a fixture produces is registered under its rule (the
    pragma-matching namespace and the emissions can't drift apart)."""
    for rule, tp, _tn, _ids, _n in _RULE_CASES:
        for f in lint_file(FIXTURES / tp):
            assert f.id in RULE_IDS[f.rule]


# ---- suppression parsing ----------------------------------------------


def test_suppression_same_line_above_line_and_rule_name():
    findings = lint_file(FIXTURES / "suppressed.py")
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 3
    reasons = {f.suppress_reason for f in sup}
    assert reasons == {
        "fixture: audited same-line pragma",
        "fixture: pragma on the line above",
        "fixture: rule-name match",
    }


def test_reasonless_pragma_suppresses_nothing_and_is_a_finding():
    findings = lint_file(FIXTURES / "suppressed.py")
    assert "missing-reason" in ids_of(findings)
    # The sleep the reasonless pragma sat on stays UNsuppressed.
    naked = [f for f in unsuppressed(findings)
             if f.id == "sleep-under-lock"]
    assert len(naked) == 1


def test_stale_pragma_is_flagged_only_under_full_rules():
    findings = lint_file(FIXTURES / "suppressed.py")
    assert "unused-suppression" in ids_of(findings)
    # Under a rule subset, a pragma for a disabled rule is legitimately
    # unused — hygiene must not fire.
    subset = lint_file(FIXTURES / "suppressed.py", rules=("L1",))
    assert "unused-suppression" not in ids_of(subset)


def test_pragma_inside_string_is_documentation_not_suppression():
    src = (
        "import threading, time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def tick(self):\n"
        "        doc = 'locklint: allow[sleep-under-lock] not a pragma'\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)\n"
        "        return doc\n"
    )
    findings = lint_source(src)
    assert unsuppressed(findings), "string literal must not suppress"
    assert "unused-suppression" not in ids_of(findings)


def test_hygiene_findings_are_not_suppressable():
    src = (
        "import threading, time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def tick(self):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)  # locklint: allow[all]\n"
    )
    findings = lint_source(src)
    assert {"missing-reason", "sleep-under-lock"} <= ids_of(findings)
    assert all(not f.suppressed for f in findings)


# ---- JSON report schema -----------------------------------------------


def test_json_report_schema():
    findings = lint_file(FIXTURES / "suppressed.py")
    report = to_report(findings)
    assert report["version"] == 1
    assert report["tool"] == "locklint"
    assert report["rules"] == list(RULES)
    assert report["summary"]["total"] == len(findings)
    assert (report["summary"]["suppressed"]
            + report["summary"]["unsuppressed"]
            == report["summary"]["total"])
    for obj in report["findings"]:
        assert set(obj) == {"rule", "id", "path", "line", "col",
                            "message", "suppressed", "suppress_reason"}
        assert isinstance(obj["line"], int) and obj["line"] >= 1
    # Round-trips through the wire format.
    assert json.loads(json.dumps(report)) == report


def test_parse_error_is_a_finding_not_a_crash():
    findings = lint_source("def broken(:\n", path="broken.py")
    assert ids_of(findings) == {"parse-error"}


# ---- CLI exit-code contract -------------------------------------------


def _cli(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "locklint.py"), *args],
        capture_output=True, text=True, cwd=REPO,
    )


def test_cli_exit_codes_and_json():
    dirty = _cli(str(FIXTURES / "l2_violations.py"))
    assert dirty.returncode == 1
    assert "sleep-under-lock" in dirty.stdout

    clean = _cli(str(FIXTURES / "l2_clean.py"))
    assert clean.returncode == 0

    badrule = _cli("--rules", "L9", str(FIXTURES / "l2_clean.py"))
    assert badrule.returncode == 2

    as_json = _cli("--json", str(FIXTURES / "l4_violations.py"))
    assert as_json.returncode == 1
    report = json.loads(as_json.stdout)
    assert report["summary"]["unsuppressed"] == 2


def test_cli_gate_is_green_on_the_package():
    gate = _cli(str(PACKAGE))
    assert gate.returncode == 0, gate.stdout + gate.stderr


# ---- DebugLock: the runtime half --------------------------------------


def test_debuglock_ownership_semantics():
    lock = DebugLock()
    assert not lock._is_owned()
    with lock:
        assert lock._is_owned()
        assert lock.locked()
        lock.assert_held("inside")  # no raise
        with pytest.raises(LockDisciplineError):
            lock.acquire()          # relock = eager self-deadlock report
    assert not lock._is_owned()
    with pytest.raises(LockDisciplineError):
        lock.release()              # releasing an unheld lock
    with pytest.raises(LockDisciplineError):
        lock.assert_held("outside")


def test_debuglock_ownership_is_per_thread():
    lock = DebugLock()
    lock.acquire()
    seen = {}

    def other():
        seen["owned"] = lock._is_owned()
        seen["got"] = lock.acquire(blocking=False)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen == {"owned": False, "got": False}
    lock.release()


def test_condition_adopts_debuglock_ownership():
    """The CPython Condition duck-typing seam: Condition(DebugLock())
    must wait/notify normally AND reject un-owned notifies with a
    thread-accurate check."""
    lock = DebugLock()
    cond = threading.Condition(lock)
    box = []

    def producer():
        with cond:
            box.append(1)
            cond.notify_all()

    with pytest.raises(RuntimeError):
        cond.notify_all()  # not held: Condition consults _is_owned
    t = threading.Thread(target=producer)
    with cond:
        t.start()
        while not box:
            cond.wait(timeout=5.0)
    t.join()
    assert box == [1]
    assert not lock._is_owned()


def test_debugcondition_requires_introspectable_lock():
    DebugCondition(DebugLock())          # fine
    DebugCondition()                     # default-constructs one
    with pytest.raises(TypeError):
        DebugCondition(threading.Lock())  # cannot report ownership


def test_assert_held_degrades_on_plain_lock():
    plain = threading.Lock()
    assert_held(plain, "anything")  # no owner concept -> no-op
    assert isinstance(make_lock(False), type(plain))
    assert isinstance(make_lock(True), DebugLock)


def test_instrument_locked_methods_enforces_contract():
    class Thing:
        def __init__(self):
            self.n = 0

        def bump_locked(self):
            self.n += 1

        def read(self):
            return self.n

    lock = DebugLock()
    thing = Thing()
    assert instrument_locked_methods(thing, lock) == 1
    with pytest.raises(LockDisciplineError):
        thing.bump_locked()
    with lock:
        thing.bump_locked()
    assert thing.read() == 1


# ---- live server under debug locks ------------------------------------


def _small_server(**kw):
    import jax

    from kvedge_tpu.models import TransformerConfig, init_params
    from kvedge_tpu.models.serving import PagedGenerationServer

    cfg = TransformerConfig(vocab=128, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_seq=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return PagedGenerationServer(params, cfg, slots=2, pages=24,
                                 page_size=4, **kw)


def test_server_debug_locks_bit_identical_and_asserting():
    prompt = [3, 1, 4, 1, 5]
    plain = _small_server()
    try:
        expect = plain.submit(prompt, 8)
    finally:
        plain.close()

    srv = _small_server(debug_locks=True)
    try:
        # the work lock keeps its own account (tracing.TimedLock) over
        # whichever lock the knob chose: the two compose
        assert isinstance(srv._lock.inner, DebugLock)
        got = srv.submit(prompt, 8)
        assert got == expect  # assertions change nothing observable
        names = [n for n in dir(type(srv)) if n.endswith("_locked")]
        assert names, "serving lost its *_locked contract surface?"
        with pytest.raises(LockDisciplineError):
            getattr(srv, names[0])()
        # Under the lock the same instrumented method binding is
        # callable (TypeError for missing args is fine — the
        # ownership gate sits in front of the call).
        with srv._lock:
            srv._free_pages_locked() if hasattr(
                srv, "_free_pages_locked") else None
    finally:
        srv.close()


def test_config_knob_parses_validates_and_threads():
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig,
        RuntimeConfigError,
    )

    assert RuntimeConfig.parse("").serving_debug_locks is False
    cfg = RuntimeConfig.parse(
        "[payload]\nserving_debug_locks = true\n"
    )
    assert cfg.serving_debug_locks is True
    assert "serving_debug_locks = true" in cfg.to_toml()
    # Round-trip: parse(to_toml()) preserves the knob.
    assert RuntimeConfig.parse(cfg.to_toml()).serving_debug_locks is True
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse(
            "[payload]\nserving_debug_locks = 'yes'\n"
        )
