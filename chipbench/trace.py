"""Reduction of one profiler trace to the numbers the metrics read.

The profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/<time>/``;
``jax.profiler.ProfileData`` reads it. Device planes (``/device:TPU:<i>``)
carry the operations that ran on each chip, host planes the threads of the
process with the benchmark's own spans (``chipbench.<entry>``) and JAX's.

- the window: from the first benchmark span's start to the last one's end;
- busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices;
- kernel time: the summed durations of the operations whose HLO
  instruction the kernel's matcher accepts;
- idle gaps: the holes in the busy union, each put down to the innermost
  host span that covers its middle.

``summarize`` takes the file; ``TraceSummary`` works on plain event tuples,
so the tests can feed it a small recorded trace.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

# the planes of the accelerator's chips, and the line that holds one event
# per operation executed (a loop's event spans the events of its body)
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# idle gaps put down to a host span one by one; the rest are summed
ATTRIBUTED_GAPS = 200


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    """Device operations per device, host spans per thread, and the window."""

    device_ops: dict  # device name -> [Event]
    host_spans: dict  # thread name -> [Event]
    span_prefix: str = "chipbench."

    def __post_init__(self):
        spans = [e for evs in self.host_spans.values() for e in evs
                 if e.name.startswith(self.span_prefix)]
        if spans:
            self.t0 = min(e.start_ns for e in spans)
            self.t1 = max(e.end_ns for e in spans)
        else:
            all_ops = [e for evs in self.device_ops.values() for e in evs]
            self.t0 = min((e.start_ns for e in all_ops), default=0.0)
            self.t1 = max((e.end_ns for e in all_ops), default=0.0)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy(self, evs):
        return _union(_clip([(e.start_ns, e.end_ns) for e in evs], self.t0, self.t1))

    @property
    def busy_s(self) -> float:
        if not self.device_ops:
            return 0.0
        tot = sum(e - s for evs in self.device_ops.values() for s, e in self._busy(evs))
        return tot * 1e-9 / len(self.device_ops)

    def ops_in_window(self):
        for evs in self.device_ops.values():
            for e in evs:
                if e.end_ns > self.t0 and e.start_ns < self.t1:
                    yield e

    def kernel_time_s(self, match) -> float | None:
        """Summed device time of the operations whose name ``match``
        accepts, per device; None when no such operation ran."""
        hits = [e.dur_ns for e in self.ops_in_window() if match(e.name)]
        if not hits:
            return None
        return sum(hits) * 1e-9 / len(self.device_ops)

    def idle_gaps(self):
        """[(start, end)] of the holes in the first device's busy union."""
        if not self.device_ops:
            return []
        busy = self._busy(next(iter(self.device_ops.values())))
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def main_thread(self) -> list:
        """The host thread that holds the benchmark's spans."""
        for evs in self.host_spans.values():
            if any(e.name.startswith(self.span_prefix) for e in evs):
                return evs
        return []

    def host_activity(self, t: float, thread: list) -> str:
        """The innermost span of ``thread`` covering time t."""
        best = None
        for e in thread:
            if e.start_ns <= t <= e.end_ns and (best is None or e.dur_ns < best.dur_ns):
                best = e
        return best.name if best is not None else "(no host span)"

    def leaf_ops(self):
        """Operations in the window that hold no other operation (a loop's
        own event is left out, its body's events stay)."""
        evs = sorted(self.ops_in_window(), key=lambda e: (e.start_ns, -e.dur_ns))
        for i, e in enumerate(evs):
            if i + 1 == len(evs) or evs[i + 1].start_ns >= e.end_ns:
                yield e

    def breakdown(self, top: int = 10) -> dict:
        ops = collections.Counter()
        for e in self.leaf_ops():
            ops[e.name.split(" = ")[0]] += e.dur_ns
        n_dev = max(len(self.device_ops), 1)
        gaps = collections.Counter()
        ranked = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])
        thread = self.main_thread()
        for s, e in ranked[:ATTRIBUTED_GAPS]:
            gaps[self.host_activity(0.5 * (s + e), thread)] += e - s
        rest = sum(e - s for s, e in ranked[ATTRIBUTED_GAPS:])
        if rest:
            gaps[f"(gaps shorter than the {ATTRIBUTED_GAPS} longest)"] += rest
        return {
            "device_ops": [[k, v * 1e-9 / n_dev] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(top)],
        }


def summarize(trace_dir: str, span_prefix: str = "chipbench.") -> TraceSummary:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    device_ops, host_spans = {}, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        Event(e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.duration_ns) for e in line.events
                       if e.duration_ns > 0]
                if evs:
                    host_spans[f"{plane.name}/{line.name}"] = evs
    return TraceSummary(device_ops, host_spans, span_prefix)
