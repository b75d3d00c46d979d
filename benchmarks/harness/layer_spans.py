"""A traced decode step cut into its layers by the kernels each layer runs
once: for the metrics of a model whose layers hold more than one kernel
(two mixers side by side). Built on `decode_events.kernels_by_step`; a
program that lacks one of the kernels gives None, and the metric leaves its
line."""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from benchmarks.harness.decode_events import kernels_by_step, steps
from benchmarks.harness.xplane import Event

Span = Tuple[float, float]


def layer_spans(run: dict, kernels: Sequence[str]
                ) -> Optional[List[Tuple[Event, List[Span]]]]:
    """For each traced execution of the decode program that holds as many
    events of each of `kernels` as of the others (one a layer: the i-th of
    each name is layer i's), the program's event and a (first start, last
    end) a layer over those kernels' events. A step cut by the trace's edge
    is left out. None where there is no step or a kernel has no event."""
    by_kernel = [kernels_by_step(run, name) for name in kernels]
    if any(found is None for found in by_kernel):
        return None
    out = []
    for program, *events in zip(steps(run), *by_kernel):
        if events[0] and len({len(evs) for evs in events}) == 1:
            out.append((program, [
                (min(e.start for e in layer), max(e.end for e in layer))
                for layer in zip(*events)]))
    return out
