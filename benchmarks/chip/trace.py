"""Reduce a profiler trace (``.xplane.pb``) to device op intervals.

The device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per HLO operation executed, named by the operation's HLO text
(``%block_fft.1 = (...) custom-call(...)``), on the host's clock. The
harness marks its measured window on the host with a
``jax.profiler.TraceAnnotation`` named :data:`WINDOW` (and the ABFT cell's
plain comparison with :data:`PLAIN`). Everything here counts operations
that start inside such a window.

* busy time: the union of the op intervals; idle share = 1 - busy/window;
* kernel time: the ops whose HLO name (without its ``.<k>`` suffix) is one
  of the configuration's kernel names;
* collective time: the ops whose opcode is a collective;
* the breakdown: the device ops that took most time, and the longest idle
  gaps, summed by the host event that was running through each.
"""
from __future__ import annotations

import collections
import dataclasses
import re

WINDOW = "bench_window"
PLAIN = "bench_plain"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast")
TOP = 10
# idle gaps per chip that the breakdown names by their host event
GAPS_NAMED = 50

_NAME = re.compile(r"^%?([\w\-\.]+)")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def profile_options():
    """Profiler options for a traced run: device and host events, no Python
    function tracer and no HLO protos (both cost time and bytes)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


@dataclasses.dataclass(frozen=True)
class Op:
    name: str       # HLO instruction name, e.g. "block_fft.1"
    opcode: str     # e.g. "custom-call", "fusion", "all-to-all"
    label: str      # name plus a custom call's target
    start: int      # ns
    end: int        # ns

    @property
    def base(self) -> str:
        """The name without XLA's ``.<k>`` uniquifier."""
        return re.sub(r"\.\d+$", "", self.name)


def parse_op(text: str, start: int, dur: int) -> Op:
    head, _, rest = text.partition(" = ")
    m = _NAME.match(head)
    name = m.group(1) if m else head
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    label = name
    t = _TARGET.search(rest)
    if t:
        label = f"{name} [{t.group(1)}]"
    return Op(name, opcode, label, start, start + dur)


def union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduction:
    windows: dict            # annotation name -> (start_ns, end_ns)
    ops: list                # per chip: list[Op], sorted by start
    host: list               # (name, start_ns, end_ns) host events
    window: str = WINDOW

    def span(self, window: str | None = None) -> tuple[int, int]:
        name = window or self.window
        if name not in self.windows:
            raise KeyError(f"the trace holds no {name!r} annotation; it "
                           f"holds {sorted(self.windows)}")
        return self.windows[name]

    def ops_in(self, chip: int, window: str | None = None) -> list[Op]:
        lo, hi = self.span(window)
        return [o for o in self.ops[chip] if lo <= o.start < hi]

    def op_ns(self, window: str | None = None, pred=None) -> float:
        """Summed duration of the window's ops (those ``pred`` accepts),
        averaged over chips."""
        tot = [sum(o.end - o.start for o in self.ops_in(c, window)
                   if pred is None or pred(o))
               for c in range(len(self.ops))]
        return sum(tot) / len(tot)

    def busy_ns(self, chip: int, window: str | None = None) -> int:
        lo, hi = self.span(window)
        return union_ns((o.start, min(o.end, hi))
                        for o in self.ops_in(chip, window))

    @property
    def window_s(self) -> float:
        lo, hi = self.span()
        return (hi - lo) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns(c) for c in range(len(self.ops))) \
            / len(self.ops) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def host_during(self, lo: int, hi: int) -> str:
        """The host event that covers most of ``[lo, hi]`` (the shortest
        such, where several cover it), or ``"no host event"``."""
        best, key = "no host event", None
        for name, s, e in self.host:
            cover = min(e, hi) - max(s, lo)
            if cover <= 0:
                continue
            k = (-cover, e - s)
            if key is None or k < key:
                best, key = name, k
        return best

    def breakdown(self) -> dict:
        chips = len(self.ops)
        by_op = collections.Counter()
        for c in range(chips):
            for o in self.ops_in(c):
                by_op[o.label] += (o.end - o.start) / chips / 1e9
        lo, hi = self.span()
        by_gap = collections.Counter()
        for c in range(chips):
            ivs = [(o.start, o.end) for o in self.ops_in(c)]
            longest = sorted(gaps(ivs, lo, hi), key=lambda g: g[0] - g[1])
            for s, e in longest[:GAPS_NAMED]:
                by_gap[self.host_during(s, e)] += (e - s) / chips / 1e9
        return {"device_ops": [[k, v] for k, v in by_op.most_common(TOP)],
                "idle_gaps": [[k, v] for k, v in by_gap.most_common(TOP)]}


def is_collective(op: Op) -> bool:
    return any(op.opcode.startswith(c) for c in COLLECTIVES)


def reduce(path: str, chips: int, window: str = WINDOW) -> Reduction:
    """Read the trace at ``path`` for devices ``0 .. chips-1``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops = {}
    windows = {}
    host = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            if idx >= chips:
                continue
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [parse_op(e.name, int(e.start_ns),
                                     int(e.duration_ns))
                            for e in line.events]
            ops[idx] = sorted(evs, key=lambda o: o.start)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    s, d = int(e.start_ns), int(e.duration_ns)
                    if e.name in (WINDOW, PLAIN):
                        windows[e.name] = (s, s + d)
                    else:
                        host.append((e.name, s, s + d))
    missing = [i for i in range(chips) if i not in ops]
    if missing:
        raise ValueError(f"the trace at {path} has no device plane for "
                         f"chips {missing}")
    return Reduction(windows=windows, ops=[ops[i] for i in range(chips)],
                     host=host, window=window)
