"""Share of the traced span the step thread spent in
`engine.wait_for_work`: no request was waiting or running, so the device's
idle time there is the traffic's and not the host's."""
from benchmarks.harness.spans import WAIT, of_run


def read(run):
    r = of_run(run)
    if r is None:
        return None
    return 100.0 * sum(s.dur for s in r.named(WAIT)) / \
        run["result"]["traced"]["window_s"]
