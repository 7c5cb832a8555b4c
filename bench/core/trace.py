"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line has an event per executed HLO operation, and the host
plane (``/host:CPU``) whose threads carry the benchmark's own spans
(``bench.*``). Both are on one clock. Operations nest (a ``while`` holds
the ops of its body), so busy time is the union of intervals and an op's
time in the breakdown is its self time. A Pallas kernel is the op whose
text names ``custom_call_target="tpu_custom_call"``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

__all__ = ["Op", "TraceSummary", "reduce_trace", "union_ns", "self_times"]

MOSAIC = 'custom_call_target="tpu_custom_call"'
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


@dataclass(frozen=True)
class Op:
    """One device operation: its HLO instruction name and interval (ns)."""

    name: str
    start: float
    end: float
    mosaic: bool


def _op_name(text: str) -> str:
    head = text.split(" = ", 1)[0].lstrip("%")
    return head + (" (pallas)" if MOSAIC in text else "")


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(ops) -> dict:
    """Self time (ns) by op name: each op's duration less its nested ops'."""
    out: dict = {}
    stack: list = []  # [end, name, child_ns]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[3] - item[2]

    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0] <= op.start:
            close(stack.pop())
        if stack:
            stack[-1][2] += op.end - op.start
        stack.append([op.end, op.name, 0.0, op.end - op.start])
    while stack:
        close(stack.pop())
    return out


@dataclass
class TraceSummary:
    """What a traced run's metrics read.

    A traced run writes two traces: the measured window, and after it the
    comparison launches (``compare``), so every op of a trace belongs to
    its phase whatever the skew between the host's and the device's clocks
    (about a millisecond on a v5e host).

    Attributes:
        window: ``(start, end)`` ns of the ``bench.window`` span (host clock).
        device_ops: Per chip, every op of the trace.
        spans: The benchmark's host spans, ``(name, start, end)``.
        compare: The comparison launches' trace, when the run took one.
    """

    window: tuple
    device_ops: list
    spans: list = field(default_factory=list)
    compare: "TraceSummary | None" = None

    @property
    def window_s(self) -> float:
        """Length of the traced window, seconds."""
        return (self.window[1] - self.window[0]) / 1e9

    def ops(self, chip: int | None = None) -> list:
        """Ops of one chip, or of all."""
        chips = self.device_ops if chip is None else self.device_ops[chip:chip + 1]
        return [o for ops in chips for o in ops]

    def kernel_ns(self) -> float:
        """Summed device time of the Pallas kernels, over all chips."""
        return sum(o.end - o.start for o in self.ops() if o.mosaic)

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over chips."""
        per_chip = [union_ns((o.start, o.end) for o in ops) for ops in self.device_ops]
        return sum(per_chip) / max(1, len(per_chip)) / 1e9

    def idle_gaps(self, chip: int = 0) -> list:
        """Idle intervals ``(start, end)`` between one chip's ops, longest first.

        The idle time before the first op and after the last (dispatch
        latency, the final wait) is ``window_s - busy`` less their sum.
        """
        gaps, cur = [], None
        for s, e in sorted((o.start, o.end) for o in self.ops(chip)):
            if cur is not None and s > cur:
                gaps.append((cur, s))
            cur = e if cur is None else max(cur, e)
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def host_span_at(self, t: float) -> str:
        """The innermost benchmark span (other than the window) open at ``t``."""
        open_ = [(e - s, n) for n, s, e in self.spans
                 if s <= t < e and n != SPAN_PREFIX + "window"]
        return min(open_)[1] if open_ else "outside bench spans"

    def breakdown(self) -> dict:
        """The ops that took most self time, and the longest idle gaps,
        each named by the host span open at its middle."""
        self_ns = self_times(self.ops(chip=0))
        top = sorted(self_ns.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": [[self.host_span_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in self.idle_gaps()[:TOP]]}


def _xplane(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_trace(path, n_devices: int = 1) -> TraceSummary:
    """Read a trace file (or the directory a trace was written to)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(_xplane(path)))
    spans, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [Op(_op_name(e.name), e.start_ns, e.end_ns, MOSAIC in e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[int(plane.name[len(DEVICE_PREFIX):])] = ops
        else:
            spans += [(e.name, e.start_ns, e.end_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    device_ops = [devices[i] for i in sorted(devices)][:n_devices]
    windows = [(s, e) for n, s, e in spans if n == SPAN_PREFIX + "window"]
    every = [o for ops in device_ops for o in ops]
    window = windows[0] if windows else (
        min((o.start for o in every), default=0.0),
        max((o.end for o in every), default=0.0))
    return TraceSummary(window=window, device_ops=device_ops, spans=spans)
