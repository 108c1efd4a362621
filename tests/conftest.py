# NOTE: deliberately no XLA_FLAGS here — smoke tests and benches must see
# the real single CPU device; only repro/launch/dryrun.py forces 512.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The `dev` extra installs `hypothesis`; where it is absent, fall back to
# the deterministic shim so the property tests still collect and run.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    from repro.testing import hypothesis_fallback
    hypothesis_fallback.install(sys.modules)


# ---------------------------------------------------------------------------
# Per-test duration budget (CI speed guard): with PYTEST_TEST_BUDGET_S set,
# any non-slow test whose call phase exceeds the budget fails the session —
# tier-1 must stay fast as the suite grows; long-running coverage belongs in
# the `slow` tier the nightly campaign runs.
# ---------------------------------------------------------------------------
def _budget_s() -> float:
    try:
        return float(os.environ.get("PYTEST_TEST_BUDGET_S", "0") or 0.0)
    except ValueError:
        return 0.0


def pytest_runtest_logreport(report):
    budget = _budget_s()
    if (budget and report.when == "call" and report.duration > budget
            and "slow" not in report.keywords):
        _OFFENDERS.append((report.nodeid, report.duration))


_OFFENDERS = []


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    budget = _budget_s()
    if not (budget and _OFFENDERS):
        return
    terminalreporter.write_sep(
        "=", f"DURATION BUDGET EXCEEDED ({budget:.0f}s per non-slow test)")
    for nodeid, dur in _OFFENDERS:
        terminalreporter.write_line(f"  {dur:7.1f}s  {nodeid}")
    terminalreporter.write_line(
        "mark long tests with @pytest.mark.slow or speed them up")


def pytest_sessionfinish(session, exitstatus):
    if _OFFENDERS and session.exitstatus == 0:
        session.exitstatus = 1
