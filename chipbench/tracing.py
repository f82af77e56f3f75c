"""The profiler trace of a window, and its reduction to what the per-layer
metrics read.

`Capture` traces the window with JAX's profiler (Python tracing off, host
annotations on) into .chipbench/trace/, reads the .xplane.pb back with
`jax.profiler.ProfileData`, keeps only the events below and deletes the
files.  `extract` keeps:
  * per TPU plane, the "XLA Ops" line (every operation the device ran)
    and the "XLA Modules" line (every execution of a compiled program,
    named by the jitted function);
  * the host annotations this harness writes, named "chipbench.<span>".
Operation names are cut to the HLO instruction's name without its
number ("%fusion.137 = ..." -> "fusion"), and each operation is credited
with its self time (its span less the operations nested in it).
`reduce` works on that extract alone, so tests can feed it a recorded one
(chipbench/tests/data/).  Times stay in the trace's own nanoseconds; the
window is the "chipbench.window" annotation.  Busy time is the union of
operation intervals inside the window, averaged over the chips used; an
idle gap is attributed to the innermost harness span that covers its
midpoint ("none" if none does)."""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil

from chipbench import common

OPS, PROGRAMS = "XLA Ops", "XLA Modules"
TOP = 10


def extract(pb_path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(pb_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {l.name: l for l in plane.lines}
            if OPS not in lines:
                continue
            devices[plane.name] = {
                key: [[e.start_ns, e.end_ns, short(e.name)]
                      for e in lines[line].events]
                for key, line in (("ops", OPS), ("programs", PROGRAMS))
                if line in lines}
        elif plane.name.startswith("/host:"):
            host += [[e.start_ns, e.end_ns, e.name[len("chipbench."):]]
                     for l in plane.lines for e in l.events
                     if e.name.startswith("chipbench.")]
    return {"devices": devices, "host": sorted(host)}


def short(name: str) -> str:
    """'%fusion.137 = bf16[...] fusion(...)' -> 'fusion';
    'jit__decode_impl(123)' -> 'jit__decode_impl'."""
    name = name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def _self_times(events):
    """{name: seconds} of each operation's own time: nested operations
    (an op inside a while loop's body) are taken out of their parent."""
    out, stack = {}, []
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out[n] = out.get(n, 0.0) + (e - s) * 1e-9
        if stack:
            parent = stack[-1][2]
            out[parent] -= (min(e, stack[-1][1]) - s) * 1e-9
        stack.append((s, e, n))
    return out


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def _union(intervals):
    merged = []
    for s, e in sorted((s, e) for s, e, _ in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _in_program(progs, t) -> str:
    """'<program>/' of the program execution running at t, else ''."""
    i = bisect.bisect_right(progs, (t, float("inf"), "")) - 1
    if i >= 0 and progs[i][0] <= t <= progs[i][1]:
        return progs[i][2].removeprefix("jit_") + "/"
    return ""


def reduce(ex: dict, device_kind: str) -> dict | None:
    """busy_s, window_s, programs {name: [executions, s]}, ops {name: s},
    gaps {host span: idle s}; None if the trace has no device or no
    window annotation."""
    windows = [(s, e) for s, e, n in ex["host"] if n == "window"]
    if not ex["devices"] or not windows:
        return None
    lo, hi = windows[0]
    busy, programs, ops, gaps = 0.0, {}, {}, {}
    spans = [(s, e, n) for s, e, n in ex["host"] if n != "window"]
    for dev in ex["devices"].values():
        dev_ops = _clip(dev["ops"], lo, hi)
        merged = _union(dev_ops)
        busy += sum(e - s for s, e in merged)
        for s, e, n in _clip(dev.get("programs", []), lo, hi):
            c = programs.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
        progs = sorted(_clip(dev.get("programs", []), lo, hi))
        for n, sec in _self_times([(s, e, _in_program(progs, s) + n)
                                   for s, e, n in dev_ops]).items():
            ops[n] = ops.get(n, 0.0) + sec
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                who = _covering(spans, (a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a) * 1e-9
    n = len(ex["devices"])
    return {"device_kind": device_kind, "busy_s": busy * 1e-9 / n,
            "window_s": (hi - lo) * 1e-9, "programs": programs,
            "ops": {k: v / n for k, v in ops.items()},
            "gaps": {k: v / n for k, v in gaps.items()}}


def _covering(spans, t) -> str:
    """The innermost (shortest) span around t."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def breakdown(red: dict) -> dict:
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:TOP]]
    return {"device_ops": top(red["ops"]), "idle_gaps": top(red["gaps"])}


class Capture:
    """Context manager: traces its body when `on`."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = common.OUT_DIR / "trace"
        self.reduced = None

    def __enter__(self):
        if self.on:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            pb = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                           recursive=True)
            ex = extract(pb[0])
            shutil.rmtree(self.dir, ignore_errors=True)
            self.reduced = reduce(ex, jax.devices()[0].device_kind)
        return False

    def reduce(self):
        return self.reduced

    def device_fields(self) -> dict:
        r = self.reduced or {"busy_s": 0.0, "window_s": 0.0}
        return {"busy_s": r["busy_s"], "window_s": r["window_s"]}

    def breakdown(self):
        return breakdown(self.reduced) if self.reduced else None
