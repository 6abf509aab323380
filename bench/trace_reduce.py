"""From a profiler trace of the window to the numbers the per-layer
metrics read.

``jax.profiler`` writes two files per trace under
``<dir>/plugins/profile/<time>/``: ``<host>.xplane.pb`` and
``<host>.trace.json.gz``. This module reads the second. It holds the same
events as the first, and besides their metadata (``tf_op``,
``hlo_category``), which ``jax.profiler.ProfileData`` does not expose; the
Pallas kernels are told apart by that ``tf_op`` (``.../pallas_call``
under the kernel's jit name), the sorts by ``hlo_category`` ``sort``.

What the trace holds, as seen on a TPU v5e under JAX 0.9:

- a process ``/device:TPU:<i>`` per chip, with the threads ``XLA Modules``
  (one event per run of a compiled program, named ``jit_<fn>(<hash>)``),
  ``XLA Ops`` (the HLO ops, nested: a ``while`` contains its body's ops)
  and ``Async XLA Ops`` (copies in flight, not counted as busy);
- a process ``/host:CPU`` whose ``python`` thread holds the benchmark's
  own spans (``jax.profiler.TraceAnnotation``), all named ``bench.*``;
  ``bench.window`` bounds the measured window.

Times in the file are microseconds; the summary is in seconds.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class Summary:
    window_s: float                 # length of the bench.window span
    busy_s: float                   # device-op union inside it, mean per chip
    chips: int
    op_s: dict = field(default_factory=dict)       # self time by op (tf_op)
    kind_s: dict = field(default_factory=dict)     # "pallas", "sort", "other"
    program_s: dict = field(default_factory=dict)  # time by jitted program
    gap_s: dict = field(default_factory=dict)      # idle time by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        def largest(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": largest(self.op_s), "idle_gaps": largest(self.gap_s)}


def trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one *.trace.json.gz under "
                                f"{trace_dir}, found {files}")
    return files[0]


def load(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)["traceEvents"]


def _names(events):
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            threads[e["pid"], e["tid"]] = e["args"]["name"]
    return procs, threads


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(ops):
    """Exclusive time of each op on one nested line: its duration less
    that of the ops directly inside it."""
    ops = sorted(ops, key=lambda e: (e["ts"], -e["dur"]))
    inner = [0.0] * len(ops)
    stack = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]]["ts"] + ops[stack[-1]]["dur"] <= e["ts"]:
            stack.pop()
        if stack:
            inner[stack[-1]] += e["dur"]
        stack.append(i)
    return [(e, max(0.0, e["dur"] - c)) for e, c in zip(ops, inner)]


def op_name(e) -> str:
    """An op's stable name: its ``tf_op`` (the jax function path) where
    the trace gives one, else its HLO category, else its name."""
    args = e.get("args", {})
    tf_op = args.get("tf_op", "").rstrip(":")
    return tf_op or args.get("hlo_category") or e["name"]


def op_kind(e) -> str:
    args = e.get("args", {})
    if "pallas_call" in args.get("tf_op", ""):
        return "pallas"
    if args.get("hlo_category") == "sort":
        return "sort"
    return "other"


def program_name(module: str) -> str:
    """``jit_core(8413849937112046058)`` -> ``core``."""
    m = re.match(r"jit_(.+?)\(\d+\)$", module)
    return m.group(1) if m else module


def _innermost(spans):
    """``label(t)``: the name of the shortest span around time ``t``."""
    spans = sorted((e["ts"], e["ts"] + e["dur"], e["name"]) for e in spans)
    starts = [a for a, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)

    def label(t):
        best = None
        i = bisect.bisect_right(starts, t)
        while i > 0 and starts[i - 1] >= t - longest:
            i -= 1
            a, b, name = spans[i]
            if b >= t and (best is None or b - a < best[0]):
                best = (b - a, name)
        return best[1] if best else "outside the benchmark's spans"

    return label


def summarize_events(events: list) -> Summary:
    procs, threads = _names(events)
    lines = collections.defaultdict(list)
    for e in events:
        if e.get("ph") == "X":
            lines[procs.get(e["pid"], ""), threads.get((e["pid"], e.get("tid")), "")].append(e)

    spans = [e for (p, t), evs in lines.items() if p.startswith("/host:")
             for e in evs if e["name"].startswith(SPAN_PREFIX)]
    windows = [e for e in spans if e["name"] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0 = windows[0]["ts"]
    w1 = w0 + windows[0]["dur"]

    def inside(e):
        return e["ts"] < w1 and e["ts"] + e["dur"] > w0

    devices = sorted({p for p, _ in lines if p.startswith("/device:TPU")})
    busy_per_chip, op_s, kind_s = [], collections.Counter(), collections.Counter()
    program_s = collections.Counter()
    busy_all = []
    for dev in devices:
        ops = [e for e in lines[dev, "XLA Ops"] if inside(e)]
        if not ops:
            continue
        union = _union((max(e["ts"], w0), min(e["ts"] + e["dur"], w1)) for e in ops)
        busy_all.append(union)
        busy_per_chip.append(sum(b - a for a, b in union))
        for e, self_us in _self_times(ops):
            op_s[op_name(e)] += self_us / 1e6
            kind_s[op_kind(e)] += self_us / 1e6
        for e in lines[dev, "XLA Modules"]:
            if inside(e):
                program_s[program_name(e["name"])] += e["dur"] / 1e6
    chips = len(busy_per_chip)
    busy_s = sum(busy_per_chip) / chips / 1e6 if chips else 0.0

    # Idle gaps of the first chip, each named by the innermost benchmark
    # span around its middle.
    gap_s = collections.Counter()
    if busy_all:
        edges = [w0] + [x for a, b in busy_all[0] for x in (a, b)] + [w1]
        label = _innermost([e for e in spans if e["name"] != WINDOW])
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gap_s[label((a + b) / 2)] += (b - a) / 1e6
    return Summary(window_s=windows[0]["dur"] / 1e6, busy_s=busy_s, chips=chips,
                   op_s=dict(op_s), kind_s=dict(kind_s), program_s=dict(program_s),
                   gap_s=dict(gap_s))


def summarize(trace_dir: str) -> Summary:
    return summarize_events(load(trace_file(trace_dir)))
