"""From a profiler trace to device busy time, module and kernel times.

``load(path)`` reads an ``.xplane.pb`` with nothing but JAX and returns
plain event dicts; ``reduce(events, ...)`` turns them into the numbers the
per-layer metrics read.  The reduction works on the dicts alone, so it is
tested on events written out by hand (``tests/test_trace.py``).

What a TPU trace holds, as JAX's profiler lays it out (PERF.md §3 says
what a chip trace has confirmed): one plane per
device, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
executed program (named after the jitted function, e.g.
``jit_decode_banked_fn(...)``) and whose line ``XLA Ops`` has one event per
operation inside it, named by its HLO text; host planes carry the
benchmark's own ``TraceAnnotation`` spans (``bench.*``) and the serving
engine's (``serve.*``).  One of them, ``bench.traced``, runs from the
start of tracing to the window's close: it is the traced window on the
trace's own timeline, and every device event is clipped to it.

No op event holds its name stack; the compiled program's text does.
Given the programs' HLO texts, ``reduce`` joins each op to its
instruction by name within the one text of its module and credits the
op's device time once to each distinct segment of its ``op_name``
(``modules[<module>]["scopes"]``).  A segment's seconds are the union of
its ops' intervals: a loop's body ops run inside the loop's own op on
the ``XLA Ops`` line, and an instant counts once however many ops of the
segment cover it.  A module compiled to several texts
under one name (the engine's admission-wave buckets all run
``prefill_banked_fn``) cannot be told apart in the trace and gets no
scopes.
"""
from __future__ import annotations

import bisect
import collections
import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
WINDOW_SPAN = "bench.traced"
HOST_SPANS = ("bench.", "serve.")


def load(path: str) -> list:
    """Every event of the device planes' module and op lines, and every
    ``bench.*`` and ``serve.*`` host span, as {plane, line, name, start,
    end, stats} dicts (times in ns on the trace's one timeline; stats
    kept for ops and host spans)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPANS):
                    continue
                stats = {}
                if not device or line.name == OPS_LINE:
                    stats = {k: v for k, v in ev.stats
                             if isinstance(v, (str, int, float))}
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start": float(ev.start_ns),
                            "end": float(ev.end_ns), "stats": stats})
    return out


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _module_of(name: str) -> str:
    """``jit_decode_banked_fn(123)`` -> ``decode_banked_fn``."""
    base = name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


# the op itself is a custom call: a TPU trace names an op by its HLO text,
# "%sign_delta_local.12 = f32[6,11008]{...} custom-call(...)"; a fusion that
# only takes a custom call's result as an operand is not one
_CUSTOM_CALL = re.compile(r"(^|\s)custom-call\(|^%?custom-call\b")


def is_custom_call(ev: dict) -> bool:
    """A Pallas kernel's op: XLA makes it a custom call."""
    return bool(_CUSTOM_CALL.search(ev["name"])) or any(
        v in ("custom-call", "custom_call") for v in ev["stats"].values())


def op_name(name: str) -> str:
    """``%while.3 = (s32[], ...) while(...)`` -> ``%while.3``."""
    return name.split(" = ", 1)[0]


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for e in events:
        s, t = max(e["start"], lo), min(e["end"], hi)
        if t > s:
            out.append(dict(e, start=s, end=t))
    return out


# an instruction line of HLO text: "[ROOT ]%name = <shape> op(...), ...,
# metadata={op_name="jit(f)/while/body/base_gemm/dot_general" ...}"
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s.*?'
                          r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')


def name_stacks(hlo_text: str) -> dict:
    """{instruction name -> the distinct segments of its ``op_name``} for
    every instruction of one program's HLO text that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = frozenset(m.group(2).split("/"))
    return out


def _stacks_by_module(hlo: dict) -> dict:
    """{module -> name stacks} for each module that has exactly one
    text among ``hlo``'s values."""
    texts = collections.defaultdict(set)
    for text in hlo.values():
        head = text.split(None, 2)
        if len(head) > 1 and head[0] == "HloModule":
            texts[_module_of(head[1].rstrip(","))].add(text)
    return {mod: name_stacks(ts.pop()) for mod, ts in texts.items()
            if len(ts) == 1}


def reduce(events: list, hlo: dict | None = None) -> dict:
    """Busy seconds (union of op intervals inside the traced window,
    averaged over the devices), the device's idle share of that window,
    per-module counts and device seconds, custom-call seconds per module,
    the ten ops that took most time, and the ten longest idle gaps each
    named by the host span with most self time in it (else "engine
    host").  With ``hlo`` (the programs' HLO texts), a module with one
    text also gets ``scopes``: {name-stack segment -> device seconds}."""
    spans = [e for e in events if e["name"] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                         f"found {len(spans)}")
    lo, hi = spans[0]["start"], spans[0]["end"]
    window_s = (hi - lo) / 1e9
    device = _clip([e for e in events
                    if e["plane"].startswith(DEVICE_PREFIX)], lo, hi)
    devices = sorted({e["plane"] for e in device})
    ops = [e for e in device if e["line"] == OPS_LINE]
    mods = [e for e in device if e["line"] == MODULES_LINE]
    host = [e for e in events if not e["plane"].startswith(DEVICE_PREFIX)
            and e["name"] != WINDOW_SPAN]
    busy_ns = 0.0
    gaps = []
    for dev in devices:
        busy = _union([(e["start"], e["end"]) for e in ops
                       if e["plane"] == dev] or
                      [(e["start"], e["end"]) for e in mods
                       if e["plane"] == dev])
        busy_ns += sum(e - s for s, e in busy)
        if dev == devices[0]:
            edges = [lo] + [x for b in busy for x in b] + [hi]
            gaps = [(b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a]
    busy_s = busy_ns / 1e9 / max(1, len(devices))

    modules = collections.defaultdict(lambda: {"count": 0, "device_s": 0.0,
                                               "custom_call_s": 0.0})
    intervals = collections.defaultdict(list)
    for m in mods:
        name = _module_of(m["name"])
        modules[name]["count"] += 1
        modules[name]["device_s"] += (m["end"] - m["start"]) / 1e9
        intervals[m["plane"]].append((m["start"], m["end"], name))
    for ivs in intervals.values():
        ivs.sort()
    starts = collections.defaultdict(list, {
        k: [iv[0] for iv in ivs] for k, ivs in intervals.items()})
    stacks = _stacks_by_module(hlo or {})
    scopes = {mod: collections.Counter() for mod in stacks}
    # (plane, module, segment) -> the end of the time credited to it
    reach = collections.defaultdict(float)
    by_op = collections.Counter()
    for op in sorted(ops, key=lambda o: o["start"]):
        owner = op["stats"].get("hlo_module")
        owner = _module_of(owner) if owner else _enclosing(
            intervals[op["plane"]], starts[op["plane"]], op["start"])
        dur = (op["end"] - op["start"]) / 1e9
        name = op_name(op["name"])
        by_op[f"{owner}/{name}"] += dur
        if owner is not None and is_custom_call(op):
            modules[owner]["custom_call_s"] += dur
        if owner in stacks:
            for segment in stacks[owner].get(name.lstrip("%"), ()):
                key = (op["plane"], owner, segment)
                start = max(op["start"], reach[key])
                if op["end"] > start:
                    scopes[owner][segment] += (op["end"] - start) / 1e9
                    reach[key] = op["end"]
    for mod, seconds in scopes.items():
        if mod in modules:
            modules[mod]["scopes"] = dict(seconds)

    longest = sorted(gaps, reverse=True)[:10]
    idle = [[_host_cause(host, s, e), dur / 1e9] for dur, s, e in longest]
    return {"devices": len(devices), "window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "modules": {k: dict(v) for k, v in modules.items()},
            "device_ops": [[n, s] for n, s in by_op.most_common(10)],
            "idle_gaps": idle}


def _enclosing(spans: list, starts: list, t: float):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][0] <= t < spans[i][1]:
        return spans[i][2]
    return None


def _host_cause(host: list, s: float, e: float) -> str:
    """The host span with most self time in [s, e]: each instant goes to
    the shortest span that covers it, as a span nested in another is
    shorter than it."""
    inside = [h for h in host if h["end"] > s and h["start"] < e]
    cuts = sorted({s, e} | {t for h in inside for t in (h["start"], h["end"])
                            if s < t < e})
    own = collections.Counter()
    for a, b in zip(cuts, cuts[1:]):
        cover = [h for h in inside if h["start"] <= a and h["end"] >= b]
        if cover:
            own[min(cover, key=lambda h: h["end"] - h["start"])["name"]] += (
                b - a)
    return own.most_common(1)[0][0] if own else "engine host"
