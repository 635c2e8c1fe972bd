"""Reduction of a JAX profiler trace to what the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX
alone, into plain spans (seconds on the trace's one clock):

* per chip, every device operation (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane) and every program execution (``XLA
  Modules``);
* the host spans the harness records around its own calls
  (``jax.profiler.TraceAnnotation`` names starting ``bench.``).

The rest is arithmetic on spans: busy time as the union of operation
intervals, idle gaps between them, time by name, and each gap named by
the host span that covers most of it. Nothing here touches a device.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute", re.I)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict          # chip -> [Span] device operations
    modules: dict      # chip -> [Span] program executions
    host: list         # [Span] the harness's own host spans

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        """Start and end of the host span ``name`` (the measured window)."""
        spans = [s for s in self.host if s.name == name]
        if not spans:
            raise ValueError(f"the trace holds no host span {name!r}")
        return spans[0].start, spans[0].end


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (or a gzip of one) into a ``Trace``."""
    import gzip
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest[chip].extend(
                        Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(ops=dict(ops), modules=dict(modules), host=host)


def clip(spans, lo: float, hi: float) -> list:
    """Spans cut to ``[lo, hi]``; those outside it dropped."""
    return [Span(s.name, max(s.start, lo), min(s.end, hi)) for s in spans
            if s.end > lo and s.start < hi]


def union(spans) -> list:
    """Merged ``(start, end)`` intervals covered by any span."""
    out = []
    for s in sorted(spans, key=lambda s: s.start):
        if out and s.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s.end)
        else:
            out.append([s.start, s.end])
    return [tuple(iv) for iv in out]


def busy_s(spans, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which any of ``spans`` runs."""
    return sum(e - s for s, e in union(clip(spans, lo, hi)))


def idle_gaps(spans, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch of ``[lo, hi]`` that no span
    covers, longest first."""
    gaps, t = [], lo
    for s, e in union(clip(spans, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def seconds_by_name(spans, lo: float, hi: float) -> dict:
    out = defaultdict(float)
    for s in clip(spans, lo, hi):
        out[s.name] += s.dur
    return dict(out)


def name_gap(gap, host) -> str:
    """The host span that overlaps ``gap`` most; among equal overlaps the
    shortest (innermost). ``"(no host span)"`` where none does."""
    best, key = "(no host span)", (0.0, 0.0)
    for s in host:
        ov = min(gap[1], s.end) - max(gap[0], s.start)
        if ov > 0 and (ov, -s.dur) > key:
            best, key = s.name, (ov, -s.dur)
    return best


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Device operations that took most time (mean over chips) and the
    longest idle gaps (of the first chip) by what the host was doing."""
    chips = sorted(trace.ops)
    by = defaultdict(float)
    for c in chips:
        for name, sec in seconds_by_name(trace.ops[c], lo, hi).items():
            by[name] += sec / len(chips)
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace.ops[chips[0]], lo, hi)[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[name_gap(g, trace.host), g[1] - g[0]]
                          for g in gaps]}


# How the metrics find the program's work in a trace. Device operations
# are named by their HLO text; a Pallas kernel is a ``tpu_custom_call``
# without its kernel's name, so the paged attention kernel is the one
# whose first operand is the flattened page table. The decode step is
# ``jit_fused(<fingerprint>)`` on one chip; on a mesh every step program
# is ``jit_body(<fingerprint>)``, and the decode step is the one that
# runs most often (every step, where an admission runs once a request).
STEP_PROGRAMS = re.compile(r"^jit_(fused|body)\(")


def paged_kernel(span: Span, table_len: int) -> bool:
    """Is ``span`` a call of the ``paged_flash_decode`` kernel over a
    page table of ``table_len`` entries?"""
    return 'custom_call_target="tpu_custom_call"' in span.name and \
        f"custom-call(s32[{table_len}]" in span.name


def decode_executions(trace: Trace, lo: float, hi: float) -> dict:
    """Per chip, the executions of the decode step program that start in
    ``[lo, hi]``."""
    out = {}
    for c, mods in trace.modules.items():
        by = defaultdict(list)
        for s in mods:
            if lo <= s.start <= hi and STEP_PROGRAMS.search(s.name):
                by[s.name].append(s)
        out[c] = max(by.values(), key=len) if by else []
    return out
