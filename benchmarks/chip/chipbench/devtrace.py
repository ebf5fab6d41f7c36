"""Device traces: capture a few rounds, reduce them to events and intervals.

A traced window is a run of whole rounds, each wrapped in the harness's own
host spans: ``chipbench.step`` around the ``FGLTrainer.step`` call (the
host dispatching the round's programs) and ``chipbench.sync`` around the
end-of-round ``block_until_ready``. The profiler writes an ``.xplane.pb``;
:func:`extract` keeps what the metrics read from it: per TPU device the op
events (line ``XLA Ops``) and program events (``XLA Modules``), and the
harness's host spans, as plain lists of ``[label, start_ns, end_ns]``. The
metric readers work on that extraction only, so a recorded one can stand in
for a chip in the tests.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

STEP, SYNC = "chipbench.step", "chipbench.sync"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def _label(event) -> str:
    """An op's name and its string stats (the HLO op name, the JAX op path
    such as ``jit(_local_rounds)/.../pallas_call``), for name matching."""
    texts = [event.name] + [v for _, v in event.stats
                            if isinstance(v, str) and 0 < len(v) <= 400]
    return " ".join(dict.fromkeys(texts))


def extract(xplane_path: str) -> Dict:
    """``{"devices": {id: {"ops": [...], "modules": [...]}}, "host": [...]}``;
    an op is ``[label, start_ns, end_ns]`` (see :func:`_label`); host spans
    carry their round as a fourth field."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out: Dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[_label(e) if key == "ops" else e.name,
                                 e.start_ns, e.end_ns] for e in line.events]
            out["devices"][plane.name[12:]] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (STEP, SYNC):
                        stats = dict(e.stats)
                        out["host"].append([e.name, e.start_ns, e.end_ns,
                                            int(stats.get("round", -1))])
    out["host"].sort(key=lambda s: s[1])
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


class Trace:
    """An extraction reduced to what the metric readers ask for."""

    def __init__(self, extraction: Dict):
        self.devices = extraction["devices"]
        self.host = extraction["host"]
        if not self.devices:
            raise RuntimeError("the trace holds no TPU device")
        steps = [h for h in self.host if h[0] == STEP]
        syncs = [h for h in self.host if h[0] == SYNC]
        if not steps or not syncs:
            raise RuntimeError("the trace holds none of the harness's spans")
        self.start, self.end = steps[0][1], syncs[-1][2]
        self.rounds = sorted({h[3] for h in steps})

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    def ops(self, dev: str):
        return [(n, max(s, self.start), min(e, self.end))
                for n, s, e in self.devices[dev]["ops"]
                if e > self.start and s < self.end]

    def busy_s(self, dev: str) -> float:
        return sum(e - s for s, e in union([(s, e) for _, s, e in self.ops(dev)])) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def module_s(self, dev: str, match) -> float:
        """Seconds of the programs whose name ``match`` accepts."""
        spans = [(s, e) for n, s, e in self.devices[dev]["modules"] if match(n)]
        return sum(e - s for s, e in union(clip(spans, self.start, self.end))) * 1e-9

    def mean_module_s(self, match) -> float:
        return sum(self.module_s(d, match) for d in self.devices) / len(self.devices)

    def op_s(self, dev: str, match) -> float:
        spans = [(s, e) for n, s, e in self.ops(dev) if match(n)]
        return sum(e - s for s, e in union(spans)) * 1e-9

    def mean_op_s(self, match) -> float:
        return sum(self.op_s(d, match) for d in self.devices) / len(self.devices)

    def idle_gaps(self, dev: str):
        """[(label, seconds)] of the device's idle gaps in the window, each
        labelled by the harness span its middle fell in."""
        busy = union([(s, e) for _, s, e in self.ops(dev)])
        gaps, t = [], self.start
        for s, e in busy + [(self.end, self.end)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            span = next((h for h in self.host if h[1] <= mid <= h[2]), None)
            label = "between spans" if span is None else (
                f"{'step dispatch' if span[0] == STEP else 'end-of-round sync'}")
            out.append((label, (e - s) * 1e-9))
        return out

    def breakdown(self, top: int = 10) -> Dict:
        """The longest device ops (seconds summed per op name, averaged over
        the chips) and the longest idle gaps of any chip."""
        totals: Dict[str, float] = {}
        for dev in self.devices:
            for label, s, e in self.ops(dev):
                name = label.split(" ", 1)[0]
                totals[name] = totals.get(name, 0.0) + (e - s) * 1e-9 / len(self.devices)
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted((g for d in self.devices for g in self.idle_gaps(d)),
                      key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}
