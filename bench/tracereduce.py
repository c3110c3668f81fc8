"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is reduced to four lists of plain tuples, so that the
arithmetic below can be checked on a trace built by hand:

    ops       (name, start_ns, end_ns, is_kernel)  device operations
    programs  (start_ns, end_ns)                   device program runs
                                                   ("XLA Modules")
    host      (name, start_ns, end_ns)             host events
    spans     (start_ns, end_ns, rows)             the benchmark's
                                                   "bench.call rows=<n>"
                                                   spans, one per
                                                   service call

Busy time is the union of the intervals of the device operations that
ran inside the benchmark's whole calls, over the window those calls
span.  An operation is placed in a call by the program run that holds
it, and a program run in the call whose span holds most of it: the
device's clock and the host's can disagree by about a millisecond,
more than a one-row call leaves between its span's start and its first
operation.  A Pallas kernel is recognised by what XLA records for the
custom call that carries it (KERNEL_RULE), not by the kernel's function
name, so naming the kernels does not move what the metrics read.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
SPAN_PREFIX = "bench.call rows="
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
KERNEL_RULE = ("a device op on the XLA Ops line whose HLO text (the "
               "event's name) holds " + KERNEL_TARGET)


def is_kernel(text: str) -> bool:
    """A Mosaic (Pallas) kernel: XLA runs it as a custom call with the
    target tpu_custom_call.  The TPU profiler names each op event by its
    instruction's HLO text, which names the target."""
    return KERNEL_TARGET in text


def op_name(text: str) -> str:
    """The instruction's name from its HLO text ("%fusion.6 = s32[..]
    fusion(...)" -> "fusion.6"); other names as they are."""
    if text.startswith("%") and " = " in text:
        return text[1:text.index(" = ")]
    return text


def union(intervals) -> int:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
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


def merged(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(ops) -> dict[str, int]:
    """Device time per op name, less the time of the ops nested inside
    it (a while loop's event spans its body's events on the same line),
    so that each nanosecond counts once, for the op that ran in it."""
    own: dict[str, int] = {}
    stack: list[tuple[str, int]] = []       # (name, end) of open ops
    # by start, the enclosing (longer) op first
    for name, s, e, _ in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            parent = stack[-1][0]
            own[parent] = own.get(parent, 0) - (e - s)
        own[name] = own.get(name, 0) + e - s
        stack.append((name, e))
    return own


@dataclass
class Reading:
    """The reduced trace.  Its window is the traced calls: from the
    first whole call's start to the last one's end (a closed loop runs
    device work only inside calls, and the profiler's own start and
    stop, which hold up the host, fall outside it)."""
    ops: list = field(default_factory=list)
    programs: list = field(default_factory=list)
    host: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    _inside: list | None = field(default=None, repr=False)

    def calls_programs(self) -> list[tuple[int, int]]:
        """The program runs of the whole calls: each run more than half
        of which lies inside one call's span (the frontend makes one
        call at a time, so spans do not overlap)."""
        spans = sorted(self.spans)
        starts = [a for a, _, _ in spans]
        kept = []
        for p0, p1 in sorted(self.programs):
            near = spans[max(bisect.bisect_right(starts, p0) - 1, 0):
                         bisect.bisect_left(starts, p1)]
            held = max((min(b, p1) - max(a, p0) for a, b, _ in near),
                       default=0)
            if 2 * held > p1 - p0:
                kept.append((p0, p1))
        return kept

    def in_spans(self):
        """Device operations of the whole calls: those inside the calls'
        program runs (`calls_programs`)."""
        if self._inside is None:
            programs = self.calls_programs()
            starts = [a for a, _ in programs]
            self._inside = []
            for op in self.ops:
                _, s, e, _ = op
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and e <= programs[k][1]:
                    self._inside.append(op)
        return self._inside

    def busy_s(self) -> float:
        """Union of the intervals of the device ops inside the calls."""
        return union((s, e) for _, s, e, _ in self.in_spans()) / 1e9

    def window_s(self) -> float:
        """From the first traced call's start to the last one's end."""
        if not self.spans:
            return 0.0
        return (max(e for _, e, _ in self.spans)
                - min(s for s, _, _ in self.spans)) / 1e9

    def kernel_time_s(self) -> float:
        """Device time of the Pallas kernels inside the calls' spans."""
        return sum(e - s for _, s, e, k in self.in_spans() if k) / 1e9

    def kernel_count(self) -> int:
        return sum(1 for *_, k in self.in_spans() if k)

    def span_rows(self) -> int:
        return sum(r for *_, r in self.spans)

    def breakdown(self, n: int = 10) -> dict:
        """The n device operations that took most time inside the calls,
        by name and self time (`self_times`), and the n longest idle
        gaps of the window, each labelled with the shortest host event
        that covers the gap's middle."""
        ops = self.in_spans()
        top = sorted(self_times(ops).items(), key=lambda kv: -kv[1])[:n]
        busy = merged((s, e) for _, s, e, _ in ops)
        if self.spans:
            lo = min(s for s, _, _ in self.spans)
            hi = max(e for _, e, _ in self.spans)
            busy = [(lo, lo)] + busy + [(hi, hi)]
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
                if b[0] > a[1]]
        gaps = sorted(gaps, reverse=True)[:n]
        labelled = []
        for length, a, b in gaps:
            mid = (a + b) // 2
            cover = [(e - s, name) for name, s, e in self.host
                     if s <= mid <= e]
            label = min(cover)[1] if cover else "no host event"
            labelled.append([label, length / 1e9])
        return {"device_ops": [[k, v / 1e9] for k, v in top],
                "idle_gaps": labelled}


def from_profile(pd) -> Reading:
    """Reduce a `jax.profiler.ProfileData` to a Reading.  A device op
    event is named by its HLO text; the Reading keeps the instruction's
    name and whether it is a kernel, worked out once per distinct op."""
    r = Reading()
    seen: dict[str, tuple[str, bool]] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == PROGRAMS_LINE:
                    r.programs += [(int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns))
                                   for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                append = r.ops.append
                for ev in line.events:
                    text = ev.name
                    known = seen.get(text)
                    if known is None:
                        known = seen[text] = (op_name(text),
                                              is_kernel(text))
                    s = int(ev.start_ns)
                    append((known[0], s, s + int(ev.duration_ns),
                            known[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    r.host.append((ev.name, s, e))
                    if ev.name.startswith(SPAN_PREFIX):
                        r.spans.append(
                            (s, e, int(ev.name[len(SPAN_PREFIX):])))
    return r


def read_dir(path) -> Reading:
    import jax
    files = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {path}, "
                           f"found {files}")
    return from_profile(jax.profiler.ProfileData.from_file(files[0]))
