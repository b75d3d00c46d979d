"""The per-test time limit of tests/conftest.py (PR 47).

Each case writes a small test file beside a conftest that is the repo's
own with `LIMIT` set low, runs pytest on it in a process of its own, and
reads the report: a test that outlives the limit fails alone, with its
name and every thread's stack, its runtime is torn down, and the tests
after it run and pass.
"""
import os
import re
import subprocess
import sys
import textwrap

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

SHIM = """
import importlib.util, signal
_spec = importlib.util.spec_from_file_location(
    "repo_conftest", {conftest!r})
_repo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_repo)
_repo.LIMIT = {limit}
globals().update({{k: v for k, v in vars(_repo).items()
                  if not k.startswith("__")}})


def pytest_runtest_logfinish(nodeid, location):
    print("TIMER_AFTER", nodeid, signal.getitimer(signal.ITIMER_REAL)[0])
"""

SLEEPER = """
import signal, time
import pytest

def test_sleeps_too_long():
    time.sleep(30)

def test_fast_after_it():
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert 0 < left <= {limit}

@pytest.mark.slow
def test_slow_has_no_limit():
    assert signal.getitimer(signal.ITIMER_REAL)[0] == 0
    time.sleep(2 * {limit})
"""

WITH_RUNTIME = """
import time
import ray_tpu

def test_waits_for_a_task_that_never_ends(ray_cluster):
    @ray_tpu.remote
    def never():
        time.sleep(600)
    ray_tpu.get(never.remote())

def test_the_runtime_is_gone():
    assert not ray_tpu.is_initialized()

def test_the_next_cluster_works(ray_cluster):
    @ray_tpu.remote
    def f(x):
        return x + 1
    assert ray_tpu.get(f.remote(1)) == 2
"""

CASES = {
    # name: (file, limit, extra arguments, the test that expires, passes)
    "sleeper": (SLEEPER, 0.3, (), "test_sleeps_too_long", 2),
    "sleeper_under_xdist": (SLEEPER, 0.3, ("-p", "xdist", "-n", "1"),
                            "test_sleeps_too_long", 2),
    "held_a_runtime": (WITH_RUNTIME, 2.0, (),
                       "test_waits_for_a_task_that_never_ends", 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_test_that_outlives_the_limit_fails_alone(case, tmp_path):
    body, limit, extra, expired, passes = CASES[case]
    (tmp_path / "conftest.py").write_text(SHIM.format(
        conftest=os.path.join(TESTS_DIR, "conftest.py"), limit=limit))
    (tmp_path / "test_inner.py").write_text(
        textwrap.dedent(body).format(limit=limit))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.dirname(TESTS_DIR), os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_inner.py", "-s", "-v",
         "--durations=0", "--durations-min=0", "-p", "no:cacheprovider",
         "--rootdir", str(tmp_path), *extra],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=90)
    out = run.stdout + run.stderr
    # the run ends, red, with the one failure and the rest green
    assert run.returncode == 1, out
    assert re.search(rf"1 failed, {passes} passed", out), out
    # the report names the test, the limit and the phase, and has the
    # stack of the thread that hung and a header for every thread
    assert f"test_inner.py::{expired} exceeded {limit:g} s in its body" \
        in out, out
    assert "most recent call first" in out
    assert re.search(rf"test_inner\.py\", line \d+ in {expired}", out), out
    # nothing stays armed between tests
    after = re.findall(r"TIMER_AFTER \S+ (\S+)", out)
    if not extra:           # an xdist worker's prints are not relayed
        assert len(after) == passes + 1 and set(after) == {"0.0"}, out
    if case == "held_a_runtime":
        assert "scheduler state at the expiry" in out
        assert "'num_pending_tasks'" in out
    else:
        # it failed at the limit, not at the end of its sleep of 30 s
        spent = re.search(rf"(\S+)s call +test_inner\.py::{expired}", out)
        assert float(spent.group(1)) < 1.0, out
