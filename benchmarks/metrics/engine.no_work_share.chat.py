"""Share of the traced window the step thread spent in
`engine.wait_for_work`: no request was waiting or running, so the device's
idle time there is the traffic's and not the host's. The window is the
marker span `run.py` read from the trace (`xplane.traced_window`), and a
wait that crosses one of its ends counts as far as it lies inside."""
from benchmarks.harness.spans import WAIT, of_run


def read(run):
    r = of_run(run)
    if r is None:
        return None
    w = run["result"]["traced"]
    inside = sum(max(0.0, min(s.end, w["hi"]) - max(s.start, w["lo"]))
                 for s in r.named(WAIT))
    return 100.0 * inside / w["window_s"]
