"""The server's own instrumentation in a profiler trace, beside what
bench/trace.py's `Reduction` keeps (device operations, bench.* spans):

  modules        program runs (module, start_ns, end_ns) from the "XLA
                 Modules" line of each /device:TPU:<n> plane. The server's
                 jitted programs are "jit_serve_<key>"
                 (launch/serve.Server._counted); every eager operation is a
                 module of its own.
  program_spans  the server's host spans (names "serve.*", with their
                 integer and string stats; launch/serve.py).

Both share the device's clock. An operation belongs to the program run in
which it starts (`ops_in`, `programs`). `host_at` names a moment by the
innermost bench.* or serve.* span open then, so `idle_gaps` names a gap
inside `Server.step` by the tick's phase. On a trace with no serve.* spans
every reading `Reduction` makes is unchanged.

The four readings at the end are the server's per-layer numbers: the
decode and prefill programs' device time, the host time of a decode tick,
and the device idle time the server's host work leaves.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools

from bench import trace

MODULES_LINE = "XLA Modules"
#: the server's host spans (launch/serve._span)
SERVE_PREFIX = "serve."
#: the server's jitted programs; device time in any other module is "eager"
PROGRAM_PREFIX = "jit_serve_"


def module(name: str) -> str:
    """"jit_serve_decode(12)" -> "jit_serve_decode" (a program id, where
    the trace appends one)."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class ProgramReduction(trace.Reduction):
    #: device plane name -> [(module, start_ns, end_ns)] sorted by start
    modules: dict = dataclasses.field(default_factory=dict)
    #: [(name, start_ns, end_ns, {stat: int or str})] of serve.* host
    #: spans, sorted by start and, at one start, the enclosing one first
    program_spans: list = dataclasses.field(default_factory=list)

    @classmethod
    def from_planes(cls, planes) -> "ProgramReduction":
        planes = list(planes)      # ProfileData hands out an iterator
        red = super().from_planes(planes)
        spans = []
        for pl in planes:
            if pl.name.startswith(trace.DEVICE_PREFIX):
                for line in pl.lines:
                    if line.name == MODULES_LINE:
                        red.modules[pl.name] = sorted(
                            ((module(ev.name), int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns))
                             for ev in line.events),
                            key=lambda r: (r[1], r[2]))
            elif pl.name.startswith("/host:"):
                for line in pl.lines:
                    for ev in line.events:
                        if ev.name.startswith(SERVE_PREFIX):
                            spans.append((
                                ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                {k: v for k, v in ev.stats
                                 if isinstance(v, (int, str))}))
        red.program_spans = sorted(spans, key=lambda s: (s[1], -s[2]))
        return red

    def host_at(self, t: int) -> str:
        """The innermost host span open at time t, or "outside": a bench.*
        span by its name after the prefix, a serve.* span by its whole
        name."""
        best = None
        for name, s, e in self.spans:
            if s <= t < e and name != "window":
                if best is None or s >= best[1]:
                    best = (name, s)
        for name, s, e, _ in self.program_spans:
            if s > t:
                break
            if t < e and (best is None or s >= best[1]):
                best = (name, s)
        return best[0] if best else "outside"

    def program_spans_in(self, lo: int, hi: int, name: str | None = None):
        """The serve.* spans (named `name`, or all) that start in [lo, hi):
        for the interval of a span, the span and all it holds."""
        out = []
        for sp in self.program_spans[bisect.bisect_left(self._starts, lo):]:
            if sp[1] >= hi:
                break
            if name is None or sp[0] == name:
                out.append(sp)
        return out

    @functools.cached_property
    def _starts(self) -> list:
        return [sp[1] for sp in self.program_spans]

    def _first_chip(self):
        planes = sorted(self.ops) or sorted(self.modules)
        return planes[0] if planes else None

    def program_runs(self, name: str, lo: int, hi: int) -> list:
        """[(start, end)] of the runs of module `name` on the first chip
        that start in [lo, hi)."""
        return [(s, e) for m, s, e in self.modules.get(self._first_chip(), [])
                if m == name and lo <= s < hi]

    def program_s(self, name: str) -> tuple[float, int]:
        """(seconds, runs) of module `name`'s runs that start in the
        window, on the first chip."""
        runs = self.program_runs(name, *self.window())
        return sum(e - s for s, e in runs) / 1e9, len(runs)

    def _program_of(self, plane: str, ops: list) -> list:
        """The module whose run each of `ops` (sorted by start) starts in,
        or None for an operation outside every run."""
        runs = self.modules.get(plane, [])
        out, i = [], 0
        for o in ops:
            while i < len(runs) and runs[i][2] <= o.start:
                i += 1
            inside = i < len(runs) and runs[i][1] <= o.start
            out.append(runs[i][0] if inside else None)
        return out

    def ops_in(self, program: str) -> list:
        """The window's operations, on every chip, that start inside a run
        of module `program`."""
        lo, hi = self.window()
        out = []
        for plane, ops in self.device_ops(lo, hi).items():
            out += [o for o, m in zip(ops, self._program_of(plane, ops))
                    if m == program]
        return out

    def programs(self) -> list:
        """[[program, seconds]] of device self time (operations that hold
        no other) by the run they start in: a jit_serve_* module by name,
        any other module as "eager", no module at all as "outside"."""
        lo, hi = self.window()
        tot: dict = {}
        for plane, ops in self.device_ops(lo, hi).items():
            for o, m in zip(ops, self._program_of(plane, ops)):
                if o.leaf:
                    key = ("outside" if m is None else
                           m if m.startswith(PROGRAM_PREFIX) else "eager")
                    tot[key] = tot.get(key, 0) + o.dur
        return [[n, v / 1e9]
                for n, v in sorted(tot.items(), key=lambda kv: -kv[1])]

    def gaps(self) -> list:
        """[(start, end)] in time order: the window's stretches with no
        device operation, on the first chip."""
        lo, hi = self.window()
        planes = self.device_ops(lo, hi)
        if not planes:
            return []
        gaps, t = [], lo
        for s, e in sorted((o.start, o.end)
                           for o in planes[sorted(planes)[0]]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def host_segments(self) -> list:
        """[(start, end, name)] in time order: the stretches in which a
        host span is open, each named as `host_at` names it."""
        spans = sorted([(s, e, n) for n, s, e in self.spans
                        if n != "window"]
                       + [(s, e, n) for n, s, e, _ in self.program_spans],
                       key=lambda x: (x[0], -x[1]))
        out, stack, t = [], [], None

        def close(until):
            nonlocal t
            while stack and stack[-1][1] <= until:
                _, e, n = stack.pop()
                if e > t:
                    out.append((t, e, n))
                    t = e
        for s, e, n in spans:
            close(s)
            if stack and s > t:
                out.append((t, s, stack[-1][2]))
            t = s
            stack.append((s, e, n))
        close(float("inf"))
        return out

    def idle_under(self) -> dict:
        """{host span: seconds} of the window's device idle time on the
        first chip, by the innermost host span open across it ("outside"
        where none is), named as `host_at` names spans."""
        out: dict = {}
        segs = self.host_segments()
        j = 0
        for a, b in self.gaps():
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            named, k = 0, j
            while k < len(segs) and segs[k][0] < b:
                s, e, n = segs[k]
                cut = min(b, e) - max(a, s)
                out[n] = out.get(n, 0) + cut / 1e9
                named += cut
                k += 1
            if b - a > named:
                out["outside"] = out.get("outside", 0) + (b - a - named) / 1e9
        return out


# -- the server's per-layer readings; each None where the trace holds nothing
# -- to read (a program without serve.* spans or jit_serve_* programs)

def decode_device_ms(red: ProgramReduction):
    """Model step: mean device time of a decode program run
    (jit_serve_decode) that starts in the window, on the first chip."""
    secs, runs = red.program_s(PROGRAM_PREFIX + "decode")
    return 1e3 * secs / runs if runs else None


def prefill_ms_per_ktok(red: ProgramReduction):
    """Model step: device time of the prefill program (jit_serve_prefill)
    per 1,000 real prompt tokens, over the admissions starting in the
    window that ran it: serve.admit spans holding a serve.sync of
    what=prefill, each with the prefill runs that start inside it."""
    lo, hi = red.window()
    ns = toks = 0
    for _, s, e, args in red.program_spans_in(lo, hi, "serve.admit"):
        if any(a.get("what") == "prefill"
               for *_, a in red.program_spans_in(s, e, "serve.sync")):
            toks += args["tokens"]
            ns += sum(b - a for a, b in
                      red.program_runs(PROGRAM_PREFIX + "prefill", s, e))
    return ns / 1e6 / (toks / 1e3) if toks and ns else None


def tick_host_ms(red: ProgramReduction):
    """Scheduler: mean host time of a decode-only tick starting in the
    window: a serve.step span's duration less its serve.sync children, over
    the steps that hold a serve.dispatch with slots > 0 and no serve.admit
    (the ticks decode_tick_ms selects)."""
    lo, hi = red.window()
    got = []
    for _, s, e, _ in red.program_spans_in(lo, hi, "serve.step"):
        inner = red.program_spans_in(s, e)
        if any(n == "serve.admit" for n, *_ in inner):
            continue
        if not any(n == "serve.dispatch" and a.get("slots", 0) > 0
                   for n, _, _, a in inner):
            continue
        got.append(e - s - sum(b - a for n, a, b, _ in inner
                               if n == "serve.sync"))
    return sum(got) / len(got) / 1e6 if got else None


def server_idle_share(red: ProgramReduction):
    """Scheduler: share of the window (%) in which the device is idle while
    the innermost open host span is one of the server's other than
    serve.sync (the host scheduling, admitting, dispatching, sampling or
    retiring while the chip waits)."""
    if not red.program_spans or not red.on_device():
        return None
    idle = red.idle_under()
    held = sum(v for n, v in idle.items()
               if n.startswith(SERVE_PREFIX) and n != "serve.sync")
    return 100.0 * held / red.window_s()


READINGS = {"decode_device_ms": decode_device_ms,
            "prefill_ms_per_ktok": prefill_ms_per_ktok,
            "tick_host_ms": tick_host_ms,
            "server_idle_share": server_idle_share}
