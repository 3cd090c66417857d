"""threadlint + lock-sanitizer + configlint contract tests (ISSUE 10
tentpole), mirroring ``tests/test_graphlint.py``:

* the SHIPPED tree is clean — zero unwaived findings over
  ``mx_rcnn_tpu`` for both new linters, every waiver reasoned;
* the fixture (``tests/fixtures/serve/threadlint_bad.py``) trips EVERY
  TL rule — the linter cannot silently lose a rule;
* behavioral tests per rule family (lock-cycle detection incl. the
  cross-function call closure, blocking-under-lock, thread-shared
  writes, signal handlers, Condition predicates, waivers);
* the lock-order graph dump carries the tree's real, cycle-free edges;
* the runtime sanitizer catches a REAL two-thread order inversion,
  wraps package-allocated locks transparently (BoundedQueue keeps
  working sanitized), raises in strict mode, and records hold-budget
  violations and watchdog trips;
* configlint: typo'd reads flagged, alias/getattr idioms followed,
  dead keys reported at their config.py definition line.
"""

import os
import sys
import textwrap
import threading
import time

import pytest

from mx_rcnn_tpu.analysis import sanitizer as san
from mx_rcnn_tpu.analysis import configlint, threadlint
from mx_rcnn_tpu.analysis.threadlint import RULES, lint_paths, lock_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "mx_rcnn_tpu")
FIXTURE = os.path.join(REPO, "tests", "fixtures", "serve",
                       "threadlint_bad.py")


# ---------------------------------------------------------------------------
# static pass: the shipped tree + the fixture
# ---------------------------------------------------------------------------

def test_shipped_tree_has_zero_unwaived_findings():
    findings = lint_paths([PKG])
    active = [f for f in findings if f.waived is None]
    assert active == [], "\n".join(f.render() for f in active)
    for f in findings:
        if f.waived is not None:
            assert f.waived.strip(), f.render()


def test_cli_exit_codes(capsys):
    assert threadlint.main([PKG]) == 0
    assert threadlint.main([FIXTURE]) == 1
    capsys.readouterr()


def test_fixture_trips_every_rule():
    findings = lint_paths([FIXTURE])
    codes = {f.code for f in findings}
    assert codes == set(RULES), (
        f"missing: {set(RULES) - codes}, unexpected: {codes - set(RULES)}")
    # the reasonless TL301 waiver silences its finding but raises TL001
    assert any(f.code == "TL301" and f.waived is not None for f in findings)
    assert any(f.code == "TL001" for f in findings)


def _lint_snippet(tmp_path, source, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return lint_paths([str(p)])


def test_lock_cycle_detected_and_consistent_order_clean(tmp_path):
    bad = _lint_snippet(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._x = threading.Lock()
                self._y = threading.Lock()

            def xy(self):
                with self._x:
                    with self._y:
                        pass

            def yx(self):
                with self._y:
                    with self._x:
                        pass
        """)
    assert {f.code for f in bad} == {"TL101"}
    assert len([f for f in bad if f.code == "TL101"]) == 2  # both edges
    good = _lint_snippet(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._x = threading.Lock()
                self._y = threading.Lock()

            def a(self):
                with self._x:
                    with self._y:
                        pass

            def b(self):
                with self._x:
                    with self._y:
                        pass
        """, name="good.py")
    assert [f for f in good if f.code == "TL101"] == []


def test_lock_cycle_through_call_closure(tmp_path):
    """The order graph follows calls: A holds lock1 and CALLS a helper
    that takes lock2 while B nests them the other way lexically."""
    findings = _lint_snippet(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._one = threading.Lock()
                self._two = threading.Lock()

            def grab_two(self):
                with self._two:
                    return 1

            def a(self):
                with self._one:
                    self.grab_two()

            def b(self):
                with self._two:
                    with self._one:
                        pass
        """)
    assert any(f.code == "TL101" for f in findings), \
        "\n".join(f.render() for f in findings)


def test_same_basename_modules_do_not_shadow_closure(tmp_path):
    """Regression (code-review r10): the corpus is keyed by a UNIQUE
    module id, so a sibling directory's same-named module defining a
    same-named lockless helper must not shadow the one that closes a
    deadlock cycle (the tree has serve/fleet.py vs tools/fleet.py)."""
    cyclic = """\
        import threading

        ONE = threading.Lock()
        TWO = threading.Lock()

        def grab_two():
            with TWO:
                return 1

        def a():
            with ONE:
                grab_two()

        def b():
            with TWO:
                with ONE:
                    pass
        """
    (tmp_path / "x").mkdir()
    (tmp_path / "y").mkdir()
    (tmp_path / "x" / "mod.py").write_text(textwrap.dedent(cyclic))
    (tmp_path / "y" / "mod.py").write_text(
        "def grab_two():\n    return 2\n")
    findings = lint_paths([str(tmp_path)])
    assert any(f.code == "TL101" for f in findings), \
        "\n".join(f.render() for f in findings)


def test_blocking_under_lock_flagged_outside_clean(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                with self._lock:
                    time.sleep(0.5)

            def good(self):
                with self._lock:
                    x = 1
                time.sleep(0.5)
                return x
        """)
    assert [f.code for f in findings] == ["TL301"]
    assert "bad" in findings[0].func


def test_thread_shared_write_flagged_guarded_clean(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                self.m = 0
                self._t = threading.Thread(target=self._work)

            def _work(self):
                self.n += 1          # unguarded -> TL201
                with self._lock:
                    self.m += 1      # guarded -> clean

            def read(self):
                return self.n + self.m
        """)
    assert [f.code for f in findings] == ["TL201"]
    assert "self.n" in findings[0].message


def test_signal_handler_rules(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        import signal
        import threading

        _lock = threading.Lock()

        def bad_handler(signum, frame):
            with _lock:              # TL401: lock in a handler
                pass

        def good_handler(signum, frame):
            state["flag"] = True     # flag flip only: clean

        state = {"flag": False}
        signal.signal(signal.SIGTERM, bad_handler)
        signal.signal(signal.SIGUSR1, good_handler)
        """)
    assert [f.code for f in findings] == ["TL401"]
    assert "bad_handler" in findings[0].func


def test_signal_handler_worker_thread_pattern_is_clean(tmp_path):
    """Regression (code-review r10): the documented FIX pattern — the
    handler only spawns a worker thread that does the jax work — must
    NOT be flagged (obs/profiler.py install_sigusr2 is this shape)."""
    findings = _lint_snippet(tmp_path, """\
        import signal
        import threading

        def handler(signum, frame):
            def work():
                import jax
                jax.block_until_ready(None)
            threading.Thread(target=work, daemon=True).start()

        signal.signal(signal.SIGUSR2, handler)
        """)
    assert [f for f in findings if f.code == "TL401"] == [], \
        "\n".join(f.render() for f in findings)


def test_annotated_write_does_not_dodge_tl201(tmp_path):
    """Regression (code-review r10): `self.n: int = 1` is a write like
    any other — AnnAssign must reach the shared-state check."""
    findings = _lint_snippet(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                self._t = threading.Thread(target=self._work)

            def _work(self):
                self.n: int = 1      # annotated, still unguarded

            def read(self):
                return self.n
        """)
    assert [f.code for f in findings] == ["TL201"]


def test_condition_wait_predicate_loop(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._cond = threading.Condition()
                self.ready = False

            def good(self):
                with self._cond:
                    while not self.ready:
                        self._cond.wait()

            def bad(self):
                with self._cond:
                    if not self.ready:
                        self._cond.wait()
        """)
    assert [f.code for f in findings] == ["TL501"]
    assert "bad" in findings[0].func


def test_waiver_requires_reason(tmp_path):
    reasoned = _lint_snippet(tmp_path, """\
        import threading
        import time

        L = threading.Lock()

        def f():
            with L:
                time.sleep(1)  # threadlint: disable=TL301 bench scaffold
        """)
    assert [f.code for f in reasoned] == ["TL301"]
    assert reasoned[0].waived == "bench scaffold"
    bare = _lint_snippet(tmp_path, """\
        import threading
        import time

        L = threading.Lock()

        def f():
            with L:
                time.sleep(1)  # threadlint: disable=TL301
        """, name="bare.py")
    assert "TL001" in {f.code for f in bare}


def test_lock_graph_dump_has_tree_edges_and_no_cycles():
    g = lock_graph([PKG])
    assert g["cycles"] == [], g["cycles"]
    edges = {(e["held"], e["acquired"]) for e in g["edges"]}
    # the serving queue's documented ordering: requests terminate while
    # the dispatcher holds the bucket condition (take_batch expiry)
    assert ("BoundedQueue._cond", "ServeRequest._lock") in edges, edges
    kinds = {n["id"]: n["kind"] for n in g["nodes"]}
    assert kinds.get("BoundedQueue._cond") == "Condition"


def test_list_rules_names_every_code(capsys):
    assert threadlint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in RULES:
        assert code in out


def test_cli_fails_on_missing_or_empty_paths(tmp_path, capsys):
    assert threadlint.main([str(tmp_path / "nope")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert threadlint.main([str(empty)]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------

@pytest.fixture
def armed_sanitizer():
    san.install(strict=False)
    san.reset()
    yield san
    san.reset()
    san.uninstall()


def test_sanitizer_catches_two_thread_order_inversion(armed_sanitizer):
    """A REAL inversion: thread 1 takes a->b, thread 2 takes b->a
    (sequenced so the test itself cannot deadlock)."""
    a = san.SanLock(threading.Lock(), "LockA")
    b = san.SanLock(threading.Lock(), "LockB")
    first_done = threading.Event()

    def t1():
        with a:
            with b:
                pass
        first_done.set()

    def t2():
        first_done.wait(5)
        with b:
            with a:
                pass

    th1, th2 = threading.Thread(target=t1), threading.Thread(target=t2)
    th1.start(), th2.start()
    th1.join(5), th2.join(5)
    rep = san.report()
    assert len(rep["inversions"]) == 1, rep
    inv = rep["inversions"][0]
    assert inv["held"] == "LockB" and inv["acquired"] == "LockA"
    assert not san.check_clean()
    assert san.check_problems()  # --check integration


def test_sanitizer_strict_mode_raises(armed_sanitizer):
    san._S.strict = True
    a = san.SanLock(threading.Lock(), "SA")
    b = san.SanLock(threading.Lock(), "SB")
    with a:
        with b:
            pass
    with b:
        with pytest.raises(san.SanitizerError):
            a.acquire()
        # the rejected acquire must UNWIND: the inner lock released and
        # the held-list clean, so other threads can't hang behind a
        # lock nobody will ever release (code-review r10 fix)
        assert not a.locked()
    assert [name for _, name in san._S.held()] == []


def test_sanitizer_wraps_package_locks_transparently(armed_sanitizer):
    """install() monkey-patches the factories: a BoundedQueue built
    AFTER arming carries a sanitized condition lock and keeps its full
    semantics (offer/take_batch roundtrip)."""
    import numpy as np

    from mx_rcnn_tpu.serve.queue import BoundedQueue, ServeRequest

    q = BoundedQueue(depth=4)
    assert type(q._cond._lock).__name__ == "SanRLock"
    req = ServeRequest(np.zeros((4, 4, 3), np.float32),
                       np.zeros(3, np.float32), (4, 4), None, 0.0)
    assert type(req._lock).__name__ == "SanLock"
    assert q.offer(req)
    batch = q.take_batch(max_n=1, max_delay_s=0.01)
    assert batch == [req]
    assert san.check_clean(), san.report()


def test_sanitizer_budget_and_watchdog(armed_sanitizer):
    san._S.budget_ms = 30.0
    lk = san.SanLock(threading.Lock(), "BudgetLock")
    with lk:
        time.sleep(0.06)
    rep = san.report()
    assert rep["budget_violations"], rep
    assert rep["budget_violations"][0]["lock"] == "BudgetLock"
    # watchdog: a blocked acquire past the stall threshold trips
    san._S.stall_s = 0.2
    holder_has_it = threading.Event()
    release = threading.Event()

    def holder():
        with lk:
            holder_has_it.set()
            release.wait(5)

    def blocked():
        with lk:
            pass

    th = threading.Thread(target=holder)
    tb = threading.Thread(target=blocked)
    th.start()
    holder_has_it.wait(5)
    tb.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and \
            not san.report()["watchdog_trips"]:
        time.sleep(0.05)
    release.set()
    th.join(5), tb.join(5)
    trips = san.report()["watchdog_trips"]
    assert trips and trips[0]["lock"] == "BudgetLock", trips
    assert not san.check_clean()


def test_sanitizer_off_by_default_and_env_arming(monkeypatch):
    assert not san.armed()
    assert threading.Lock is san._RAW_LOCK
    monkeypatch.setenv("MXRCNN_THREAD_SANITIZER", "0")
    assert san.maybe_install_from_env() is False
    monkeypatch.setenv("MXRCNN_THREAD_SANITIZER", "1")
    try:
        assert san.maybe_install_from_env() is True
        assert san.armed()
    finally:
        san.reset()
        san.uninstall()
    assert threading.Lock is san._RAW_LOCK


# ---------------------------------------------------------------------------
# configlint
# ---------------------------------------------------------------------------

def test_configlint_tree_clean():
    findings = configlint.lint_paths([PKG])
    active = [f for f in findings if f.waived is None]
    assert active == [], "\n".join(f.render() for f in active)
    for f in findings:
        if f.waived is not None:
            assert f.waived.strip(), f.render()


def _configlint_snippet(tmp_path, source):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(source))
    return configlint.lint_paths([str(p)])


def test_configlint_catches_typo_read(tmp_path):
    findings = _configlint_snippet(tmp_path, """\
        def f(cfg):
            return cfg.serve.batch_sz  # typo: batch_size
        """)
    bad = [f for f in findings if f.code == "CL101"]
    assert len(bad) == 1 and "serve.batch_sz" in bad[0].message


def test_configlint_follows_alias_and_getattr(tmp_path):
    findings = _configlint_snippet(tmp_path, """\
        def f(cfg):
            s = cfg.serve
            ok = s.batch_size            # valid via alias
            bad = s.wattermark           # CL101 via alias
            o = getattr(cfg, "obs", None)
            ok2 = o.enabled              # valid via getattr alias
            return ok, bad, ok2
        """)
    bad = [f for f in findings if f.code == "CL101"]
    assert len(bad) == 1 and "serve.wattermark" in bad[0].message


def test_configlint_alias_reaches_a_closure(tmp_path):
    """A function nested in another reads through the outer one's alias,
    as Python does (core/train.py's ``tr = cfg.train`` is read inside
    ``one_img``)."""
    findings = _configlint_snippet(tmp_path, """\
        def f(cfg):
            s = cfg.serve

            def g():
                return s.wattermark      # CL101 through the outer alias
            return g
        """)
    bad = [f for f in findings if f.code == "CL101"]
    assert len(bad) == 1 and "serve.wattermark" in bad[0].message


def test_configlint_getattr_key_matching_a_section_name(tmp_path):
    """Regression (code-review r10): a typo'd 2-arg getattr whose key
    happens to equal a SECTION name ('data') must still be CL101."""
    findings = _configlint_snippet(tmp_path, """\
        def f(cfg):
            s = cfg.serve
            return getattr(s, "data")    # typo, raises at runtime
        """)
    bad = [f for f in findings if f.code == "CL101"]
    assert len(bad) == 1 and "serve.data" in bad[0].message


def test_configlint_reports_dead_keys_at_definition(tmp_path):
    """A tree reading only serve.batch_size leaves (among much else)
    serve.max_delay_ms dead — reported at its config.py line."""
    findings = _configlint_snippet(tmp_path, """\
        def f(cfg):
            return cfg.serve.batch_size
        """)
    dead = {f.message.split("'")[1] for f in findings
            if f.code == "CL201" and f.waived is None}
    assert "serve.max_delay_ms" in dead
    assert "serve.batch_size" not in dead
    cl201 = [f for f in findings
             if f.code == "CL201" and f.waived is None][0]
    assert cl201.path.endswith("config.py") and cl201.line > 0


def test_configlint_property_keys_are_valid(tmp_path):
    """Derived keys (properties like network.num_anchors) are legal
    reads, not typos."""
    findings = _configlint_snippet(tmp_path, """\
        def f(cfg):
            return cfg.network.num_anchors
        """)
    assert [f for f in findings if f.code == "CL101"] == []


def test_configlint_list_rules_and_missing_paths(tmp_path, capsys):
    assert configlint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in configlint.RULES:
        assert code in out
    assert configlint.main([str(tmp_path / "nope")]) == 2
    capsys.readouterr()
