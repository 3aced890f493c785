"""Profiler trace -> device busy intervals, kernel events by name, host spans.

`Capture(log_dir)` records the JAX profiler (no Python tracer) around a
window; `Reduction.from_dir` reads the `.xplane.pb` it wrote with
`jax.profiler.ProfileData` and keeps, for the chip the run used:

  ops      device operations (name, start_ns, end_ns, stats text) from the
           "XLA Ops" line of each /device:TPU:<n> plane;
  spans    host spans that the benchmark itself opened (names "bench.*").

On a v5e the "XLA Ops" line names each operation by its HLO instruction
("%gemm.48 = bf16[2048,16384]... custom-call(...)"): a Pallas kernel by the
name of its pallas_call ("gemm", "flash_attention", "paged_flash_decode"),
a scanned layer stack by the "while" that holds its body's operations.
Kernels are found by that name (`kind`); the breakdown counts the self time
of operations that hold no other, grouped by kind, so a "while" never hides
what ran inside it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class Capture:
    """The JAX profiler (no Python tracer) around a window, marked by a
    bench.window host span: `open()` when the window opens, `close()`
    when it has closed."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._span = None

    def open(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = span("window")
        self._span.__enter__()

    def close(self) -> "Reduction":
        """Stop, read the trace back, and remove it from disk."""
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            return Reduction.from_dir(self.log_dir)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def latest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stat_text(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def kind(name: str) -> str:
    """"%gemm.48 = bf16[...] custom-call(...)" -> "gemm"."""
    head = name.split(" ", 1)[0].lstrip("%")
    base, dot, num = head.rpartition(".")
    return base if dot and num.isdigit() else head


@dataclasses.dataclass
class Op:
    name: str
    start: int          # ns
    end: int            # ns
    text: str           # name and string stats
    leaf: bool = True   # holds no other operation of its line

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def kind(self) -> str:
        return kind(self.name)


def mark_leaves(ops: list) -> list:
    """Flag the operations that contain another (sorted by start)."""
    stack: list = []
    for o in ops:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].leaf = False
        stack.append(o)
    return ops


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
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


@dataclasses.dataclass
class Reduction:
    ops: dict            # device plane name -> [Op] sorted by start
    spans: list          # [(name, start_ns, end_ns)] of bench.* host spans

    @classmethod
    def from_planes(cls, planes) -> "Reduction":
        ops, spans = {}, []
        for pl in planes:
            if pl.name.startswith(DEVICE_PREFIX):
                for line in pl.lines:
                    if line.name != OPS_LINE:
                        continue
                    got = [Op(ev.name, int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns),
                              ev.name + " " + _stat_text(ev))
                           for ev in line.events]
                    ops[pl.name] = mark_leaves(sorted(
                        got, key=lambda o: (o.start, -o.end)))
            elif pl.name.startswith("/host:"):
                for line in pl.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name[len(SPAN_PREFIX):],
                                          int(ev.start_ns),
                                          int(ev.start_ns + ev.duration_ns)))
        return cls(ops, sorted(spans, key=lambda s: s[1]))

    @classmethod
    def from_dir(cls, log_dir: str) -> "Reduction":
        from jax.profiler import ProfileData
        return cls.from_planes(
            ProfileData.from_file(latest_xplane(log_dir)).planes)

    def on_device(self) -> bool:
        """Whether any operation ran on a chip inside the window."""
        lo, hi = self.window()
        return any(self.device_ops(lo, hi).values())

    def window(self) -> tuple[int, int]:
        """[start, end) of the traced window: the bench.window span."""
        for name, s, e in self.spans:
            if name == "window":
                return s, e
        raise ValueError("the trace holds no bench.window span")

    def device_ops(self, lo: int, hi: int) -> dict:
        """Per device plane, its operations clipped to [lo, hi)."""
        out = {}
        for plane, ops in self.ops.items():
            out[plane] = [Op(o.name, max(o.start, lo), min(o.end, hi), o.text,
                             o.leaf)
                          for o in ops if o.end > lo and o.start < hi]
        return out

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        lo, hi = self.window()
        per = [union_ns((o.start, o.end) for o in ops)
               for ops in self.device_ops(lo, hi).values()]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def kernel_s(self, name: str) -> tuple[float, int]:
        """(seconds, events) of the operations of kind `name` (a Pallas
        kernel's pallas_call name) inside the window, summed over chips."""
        lo, hi = self.window()
        t, n = 0, 0
        for ops in self.device_ops(lo, hi).values():
            for o in ops:
                if o.kind == name:
                    t += o.dur
                    n += 1
        return t / 1e9, n

    def top_ops(self, k: int = 10) -> list:
        """[[kind, seconds]] of the k kinds of operation that took most
        device time, counting operations that hold no other."""
        lo, hi = self.window()
        tot: dict = {}
        for ops in self.device_ops(lo, hi).values():
            for o in ops:
                if o.leaf:
                    tot[o.kind] = tot.get(o.kind, 0) + o.dur
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9] for n, v in best]

    def idle_gaps(self, k: int = 10) -> list:
        """[[host span during the gap, seconds]] of the k longest stretches
        with no device operation, on the first chip."""
        lo, hi = self.window()
        planes = self.device_ops(lo, hi)
        if not planes:
            return []
        ops = planes[sorted(planes)[0]]
        gaps, t = [], lo
        for s, e in sorted((o.start, o.end) for o in ops):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_at((a + b) // 2), (b - a) / 1e9]
                for a, b in gaps[:k]]

    def host_at(self, t: int) -> str:
        """The innermost bench.* span open at time t, or "outside"."""
        best = None
        for name, s, e in self.spans:
            if s <= t < e and name != "window":
                if best is None or s >= best[1]:
                    best = (name, s)
        return best[0] if best else "outside"
