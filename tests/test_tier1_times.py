"""The suite's own instruments: ``tools/tier1_times.py`` (where a tier-1
run's CPU time went, and whether it kept its budgets) on a synthetic junit
file, and the root conftest's per-test clock (docs/testing.md "Budgets")."""

import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

TOOL = Path(__file__).resolve().parent.parent / "tools" / "tier1_times.py"


def _junit(cases) -> str:
    rows = "".join(
        f'<testcase classname="{cls}" name="{name}" time="{seconds}" />'
        for cls, name, seconds in cases
    )
    return f'<testsuites><testsuite name="pytest" tests="{len(cases)}">{rows}</testsuite></testsuites>'


def _run(tmp_path, cases):
    xml = tmp_path / "t1.xml"
    xml.write_text(_junit(cases))
    done = subprocess.run(
        [sys.executable, str(TOOL), str(xml)], capture_output=True, text=True, timeout=60
    )
    return done.returncode, done.stdout


@pytest.mark.parametrize("cases,code,says", [
    # inside every budget: files summed, the test of 20 s and more listed
    ([("tests.test_a", "test_x[1]", 21.5), ("tests.test_a", "test_y", 0.5),
      ("tests.test_b", "test_z", 3.0)], 0, ["22.0 s     2 tests  tests.test_a", "total 25.0 s in 3 tests of 2 files"]),
    # one file over 250 s, though no test and not the total is
    ([("tests.test_a", f"test_{i}", 26.0) for i in range(10)], 1,
     ["OVER BUDGET: file tests.test_a: 260.0 s"]),
    # one test over 150 s
    ([("tests.test_a", "test_slow", 151.0)], 1,
     ["OVER BUDGET: test tests.test_a::test_slow: 151.0 s"]),
    # the total over 3,000 s in files that each keep theirs; the wall clock of
    # six workers is the total's sixth, the largest file being under it
    ([(f"tests.test_{f}", "test_x", 149.0) for f in range(21)], 1,
     ["OVER BUDGET: total: 3129.0 s", "wall on 6 workers about 522 s"]),
], ids=["inside", "file_over", "test_over", "total_over"])
def test_tier1_times_reads_a_junit_file_and_holds_it_to_the_budgets(tmp_path, cases, code, says):
    got, out = _run(tmp_path, cases)
    assert got == code, out
    for line in says:
        assert line in out, out
    assert ("OVER BUDGET" in out) == bool(code)


def test_a_synchronous_test_has_a_clock_of_its_own():
    """The conftest's limit raises in the test (the worker lives on), lets a
    test that ends in time be, puts back the handler it found, and off the
    main thread (where no signal is taken) runs the block unlimited."""
    import signal

    clock = sys.modules["conftest"].a_clock_of_its_own
    before = signal.getsignal(signal.SIGALRM)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="tests/x.py::test_hangs ran past its 0 s"):
        with clock("tests/x.py::test_hangs", 0.2):
            while time.monotonic() - t0 < 30:
                time.sleep(0.01)
    assert time.monotonic() - t0 < 10
    with clock("tests/x.py::test_ends", 0.2):
        pass
    time.sleep(0.3)  # the timer was disarmed: nothing fires here
    assert signal.getsignal(signal.SIGALRM) is before
    seen = []

    def elsewhere():
        with clock("tests/x.py::test_off_main", 0.05):
            time.sleep(0.2)
            seen.append("ran to its end")

    thread = threading.Thread(target=elsewhere)
    thread.start()
    thread.join()
    assert seen == ["ran to its end"]
