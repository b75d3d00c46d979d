"""Test harness config.

Forces JAX onto a virtual 8-device CPU platform *before* any jax import so
sharding/mesh tests exercise real multi-device paths without TPU hardware —
the analogue of the reference's same-host multi-raylet trick
(reference python/ray/cluster_utils.py:135) per SURVEY.md §4.5.
"""
import faulthandler
import os
import signal
import tempfile

# Force CPU even on a host with a TPU: unit tests always run on the
# virtual 8-device CPU mesh, and only chip_smoke.py and the benchmark
# touch the chip. The variables reach worker processes; the config update
# covers this process, which is also what lets a test call
# __graft_entry__.dryrun_multichip(8) after the backend is up.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Keep XLA/CPU thread pools small on tiny CI boxes.
os.environ.setdefault("XLA_CPU_MULTI_THREAD_EIGEN", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

# Seconds a tier-1 test may spend in each of its phases (set-up, body,
# tear-down). A test that hangs then fails alone, with every thread's
# stack, instead of holding its xdist worker, the rest of its file and
# the whole run until the run's own clock cuts it. Five times the slowest
# tier-1 test of a whole loaded run (43.10 s: CHANGES.md, PR 47, has the
# runs), rounded up, since that is more than 120. A test that needs more is `slow`
# ("multi-minute", pytest.ini), and a `slow` test has no limit.
# pytest.ini's faulthandler_timeout, somewhat under this, is the second
# net: a hang inside a C call that holds the GIL never lets the handler
# below run.
LIMIT = 240.0
# ... and what the post-mortem and the runtime's teardown may take after
_GRACE = 20.0


class _Expired(BaseException):
    """Raised in the main thread by the alarm. Not an Exception: no
    `except Exception` of the code under test swallows it."""


def _on_alarm(signum, frame):
    # armed again at once: should an `except BaseException` swallow this
    # one, the next comes a few seconds later
    signal.setitimer(signal.ITIMER_REAL, 5.0)
    with tempfile.TemporaryFile("w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        raise _Expired(f.read())


signal.signal(signal.SIGALRM, _on_alarm)


def _give_up_runtime() -> str:
    """After an expiry: what the runtime's schedulers hold, then the
    runtime's end, so that the next test's `ray_cluster` builds a fresh
    one. Under an alarm of its own, since the runtime is what is sick;
    the workers that a shutdown which was cut leaves behind are killed."""
    import ray_tpu
    from ray_tpu._private import context
    rt = context.maybe_ctx()
    if rt is None:
        return ""
    said, pids = [], []
    signal.setitimer(signal.ITIMER_REAL, _GRACE)
    try:
        cluster = getattr(rt, "cluster", None)   # a remote driver has none
        for node in cluster.alive_nodes() if cluster is not None else ():
            rows = node.scheduler.workers_snapshot()
            pids += [r["pid"] for r in rows if r["pid"]]
            said += [repr(node.scheduler.stats())] + [repr(r) for r in rows]
        ray_tpu.shutdown()
    except (_Expired, Exception) as e:
        said.append(f"the runtime's shutdown did not end: {type(e).__name__}")
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
    if context.maybe_ctx() is not None:
        context.set_ctx(None)
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    return "\nscheduler state at the expiry:\n" + "\n".join(said)


def _time_limit(item, phase):
    slow = item.get_closest_marker("slow") is not None
    signal.setitimer(signal.ITIMER_REAL, 0 if slow else LIMIT)
    try:
        return (yield)
    except _Expired as e:
        stacks = str(e)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
    pytest.fail(f"{item.nodeid} exceeded {LIMIT:g} s in its {phase}\n"
                f"{stacks}{_give_up_runtime()}", pytrace=False)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _time_limit(item, "set-up"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _time_limit(item, "body"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _time_limit(item, "tear-down"))


@pytest.fixture(params=["native", "python"])
def wire_engine_mode(request):
    """Run a test under BOTH wire engines: the r7 native frame engine
    (C read pump / writev / envelope codec, codec force-enabled so the
    C paths are exercised even on C-protobuf hosts where 'auto' would
    defer) and the pure-Python paths (RAY_TPU_WIRE_NATIVE=0). Opt-in
    per test/file — wire-contract suites also attach it autouse."""
    import os

    from ray_tpu import native
    from ray_tpu._private.config import CONFIG

    if request.param == "native" and not native.available():
        pytest.skip("no C compiler: native frame engine unavailable")
    prev = {k: os.environ.get(k) for k in
            ("RAY_TPU_WIRE_NATIVE", "RAY_TPU_WIRE_NATIVE_CODEC")}
    if request.param == "native":
        os.environ["RAY_TPU_WIRE_NATIVE"] = "1"
        os.environ["RAY_TPU_WIRE_NATIVE_CODEC"] = "1"
    else:
        os.environ["RAY_TPU_WIRE_NATIVE"] = "0"
    CONFIG.reload()
    try:
        yield request.param
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        CONFIG.reload()


@pytest.fixture()
def ray_cluster():
    """Shared runtime: reuses a live runtime if present, (re)creates one
    otherwise (a prior fresh_cluster may have torn it down). No teardown
    — the session finalizer below shuts it down once."""
    import ray_tpu
    yield ray_tpu.init(num_cpus=4, ignore_reinit_error=True)


@pytest.fixture(scope="session", autouse=True)
def _shutdown_at_end():
    yield
    import ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


@pytest.fixture()
def fresh_cluster():
    """Isolated runtime for failure-injection tests. Tears down any
    module-scoped shared runtime first (one runtime per process)."""
    import ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def walk_budget(monkeypatch):
    """Sets `ops.paged_attention.WALK_BUFFER_BYTES` for a test, so that
    tables as small as the tests' are walked in more than one block; the
    jitted calls forget what they were traced with, before and after."""
    from ray_tpu.ops import paged_attention as pa
    calls = (pa._paged_decode_call, pa._paged_window_decode_call,
             pa._mla_paged_decode_call)

    def set_to(nbytes):
        monkeypatch.setattr(pa, "WALK_BUFFER_BYTES", nbytes)
        for call in calls:
            call.clear_cache()
    yield set_to
    for call in calls:
        call.clear_cache()
