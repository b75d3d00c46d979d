"""The part of the host's gap per decode step under `engine.lock_wait`:
the device is idle while the step thread waits for the engine's lock,
which a caller's `generate()` or `cancel()`, `engine_stats()` or a
subscriber's backlog holds. The span is written only where the lock was
not free at once, so a program that never waits, and one that has no such
span (the parent of PR 39), read 0. Idle time left under no span of the
program after this is the process standing still."""
from benchmarks.harness.spans import per_decode_step_ms

LOCK_WAIT = "engine.lock_wait"


def read(run):
    return per_decode_step_ms(run, LOCK_WAIT)
