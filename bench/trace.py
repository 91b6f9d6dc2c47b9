"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

A whole solve is millions of device operations (each auction round of a
32-wide LAP is a handful of sub-microsecond ops), so the benchmark traces a
slice of one call (see ``run.py``) and keeps only this reduction.

The reduction works on a compact form of the trace, which the test suite
also keeps as a recorded fixture::

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...}}

The traced window is the span from the first leaf op's start to the last
one's end, over all devices: the slice lies inside one call, so the device
is at work at both of its ends.

``name`` is the HLO instruction text the TPU profiler gives each op; only
the instruction name and the operand shapes of kernel calls are read.
Control-flow ops (``while``, ``conditional``, ``call``) enclose the ops of
their bodies and are left out, so busy time is the union of the leaf ops.
"""

from __future__ import annotations

import glob
import re

_NAME = re.compile(r"^%?([^\s=]+)")
_CONTROL = re.compile(r"\s(while|conditional|call)\(")
_SHAPE = re.compile(r"f32\[([0-9,]*)\]")
# characters of an op's text kept: a kernel call's name and operand shapes
_KEEP = 600


def load_xspace(trace_dir: str) -> dict:
    """The compact form of the ``.xplane.pb`` the profiler wrote under
    ``trace_dir``: the "XLA Ops" line of every TPU device plane."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    devices, kept = {}, {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = plane.name.rsplit(":", 1)[1]
        if not dev.isdigit():
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = []
            for ev in line.events:
                name = ev.name
                if name not in kept:  # one copy per distinct op
                    kept[name] = None if is_control(name) else name[:_KEEP]
                if kept[name] is not None:
                    evs.append([kept[name], ev.start_ns, ev.duration_ns])
            devices[dev] = evs
    return {"devices": devices}


def op_name(text: str) -> str:
    """The HLO instruction name of an op's text (``fusion.283``)."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def is_control(text: str) -> bool:
    return bool(_CONTROL.search(text))


def leaf_ops(events: list) -> list:
    """(text, start, end) of the ops that are not control flow."""
    return [(text, start, start + dur) for text, start, dur in events
            if not is_control(text)]


def busy_ns(ops: list) -> float:
    """Length of the union of the ops' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _t, s, e in sorted(ops, key=lambda o: o[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(ops: list, window_ns, top: int = 10) -> list:
    """The longest stretches of the window with no op on the device, each
    named by the op that ran before it."""
    gaps, cur_e, prev = [], window_ns[0], "window start"
    for text, s, e in sorted(ops, key=lambda o: o[1]):
        if s > cur_e:
            gaps.append((f"after {prev}", (s - cur_e) / 1e9))
        if e >= cur_e:
            cur_e, prev = e, op_name(text)
    if window_ns[1] > cur_e:
        gaps.append((f"after {prev}", (window_ns[1] - cur_e) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def top_ops(ops: list, top: int = 10) -> list:
    """The op names that took most device time, with their seconds."""
    tot: dict = {}
    for text, s, e in ops:
        name = op_name(text)
        tot[name] = tot.get(name, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(name, ns / 1e9) for name, ns in ranked]


def _shapes(text: str) -> list:
    return [tuple(int(v) for v in m.split(",") if v)
            for m in _SHAPE.findall(text)]


def kernel_calls(ops: list, pattern: str) -> list:
    """(f32 result shapes, f32 operand shapes, seconds) of each custom call
    whose instruction name contains ``pattern``."""
    out = []
    for text, s, e in ops:
        if pattern not in op_name(text) or "custom-call(" not in text:
            continue
        result, args = text.split("custom-call(", 1)
        out.append((_shapes(result.split(" = ", 1)[-1]),
                    _shapes(_balanced(args)), (e - s) / 1e9))
    return out


def _balanced(text: str) -> str:
    """The operand list up to the parenthesis that closes it (layouts such
    as ``T(8,128)`` nest inside it)."""
    depth = 1
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return text[:i]
    return text


def starts_of(ops: list, pattern: str) -> list:
    """Start times, in order, of the ops whose instruction name contains
    ``pattern``."""
    return sorted(s for text, s, _e in ops if pattern in op_name(text))


class Reduced:
    """What the metric readers take from a trace."""

    def __init__(self, compact: dict):
        self.ops = {dev: leaf_ops(evs)
                    for dev, evs in compact["devices"].items()}
        spans = [(min(s for _t, s, _e in ops), max(e for _t, _s, e in ops))
                 for ops in self.ops.values() if ops]
        if not spans:
            raise ValueError("the trace holds no device op")
        self.window_ns = (min(s for s, _ in spans), max(e for _, e in spans))
        self.window_s = (self.window_ns[1] - self.window_ns[0]) / 1e9
        self.busy_s = {dev: busy_ns(ops) / 1e9
                       for dev, ops in self.ops.items()}

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def all_ops(self) -> list:
        return [op for ops in self.ops.values() for op in ops]

    def first_device_ops(self) -> list:
        return self.ops[sorted(self.ops, key=int)[0]]

    def breakdown(self) -> dict:
        """Top device ops and longest idle gaps of the first device."""
        ops = self.first_device_ops()
        return {"device_ops": [list(t) for t in top_ops(ops)],
                "idle_gaps": [list(g) for g in idle_gaps(ops,
                                                         self.window_ns)]}
