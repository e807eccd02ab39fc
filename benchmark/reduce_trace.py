"""From a profiler trace (.xplane.pb) to the numbers the layer readers use.

`load` is the only part that touches JAX's reader; `reduce` is plain Python
over the loaded events, so that the harness's test can hold it to a small
recorded trace (tests/fixtures/trace_small.json) with hand-computed answers.

Loaded form:
  {"ops":   [[chip, name, start_ns, dur_ns, module, in_while], ...],
   "spans": [[name, start_ns, dur_ns], ...]}       # the client's bench.* spans

A device op is an event of a device plane's "XLA Ops" line; its module is
the "XLA Modules" event it falls into ("Async XLA Ops", whose copy-start to
copy-done spans are waits and not work, is not read).  `in_while` is true for
an op that runs inside a `while` body: the while op is an event of its own on
that line and the body's ops nest inside its interval, so nesting tells them
apart.  The while op itself counts as the loop.  The program has no
named_scope, so this is all a trace can say of fast path and slow path: the
conditional around the slow-path loop, and the state copies it makes before
the loop, stay outside; four loops of ~20 us in the fast path fall inside.

Reduced form (times in seconds):
  window_s   first bench.* span's start to the last one's end
  busy_s     union of device-op intervals inside the window, mean over chips
  chips      how many chips ran an op inside the window
  modules    {module: {"busy_s": union of its ops,
                       "while_s": union of its ops inside a while}}, each
             the mean over chips of the PER-CHIP unions: a sharded module
             runs on every chip at the same time, and its time is a
             replica's, not the overlap of all of them
  steps      [[start_s, dur_s, device busy inside it], ...] per bench.step
  top_ops    the ten op names with most device time, [[name, seconds], ...]
  top_gaps   the ten longest intervals with no op on any chip, labelled by
             what the client was in: [["step", seconds], ...]
"""

from __future__ import annotations

import re

_SPAN_PREFIX = "bench."
_WHILE = re.compile(r"^while(\.|$)")


def op_name(event_name: str) -> str:
    """The TPU names an op event by its whole HLO line ("%fusion.7 = s32[...]
    fusion(...)"); the CPU by the op's name alone.  -> "fusion.7"."""
    return event_name.split(" = ")[0].lstrip("%")


def load(path: str, peak: dict) -> dict:
    """`peak` is the device's entry of peaks.json: which planes are chips
    (`trace_planes`), which of their lines hold ops (`trace_ops_line`) and
    which module runs (`trace_modules_line`, may be absent: then an op says
    its module itself, in its `hlo_module` stat)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = [], []
    chip = 0
    for plane in data.planes:
        on_chip = bool(re.search(peak["trace_planes"], plane.name))
        runs, events = [], []
        for line in plane.lines:
            if on_chip and re.search(peak["trace_ops_line"], line.name):
                events += [(e.start_ns, e.duration_ns, e.name, e.stats)
                           for e in line.events]
            elif on_chip and re.search(peak.get("trace_modules_line", "^$"),
                                       line.name):
                runs += [(e.start_ns, e.start_ns + e.duration_ns,
                          e.name.split("(")[0]) for e in line.events]
            else:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(_SPAN_PREFIX)]
        if not on_chip:
            continue
        runs.sort()
        events.sort(key=lambda t: (t[0], -t[1]))
        open_whiles = []  # end times of the while ops we are inside
        at = 0
        for start, dur, name, stats in events:
            while at < len(runs) and runs[at][1] <= start:
                at += 1
            if at < len(runs) and runs[at][0] <= start:
                module = runs[at][2]
            else:
                module = dict(stats).get("hlo_module")
                if module is None:
                    continue  # not an op of a program: a runtime marker
            while open_whiles and start >= open_whiles[-1]:
                open_whiles.pop()
            name = op_name(name)
            is_while = bool(_WHILE.match(name))
            ops.append([chip, name, start, dur, str(module),
                        bool(open_whiles) or is_while])
            if is_while:
                open_whiles.append(start + dur)
        chip += 1
    return {"ops": ops, "spans": sorted(spans, key=lambda s: s[1])}


def _union(intervals: list) -> list:
    """Sorted disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def _length(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def reduce(loaded: dict) -> dict:
    spans = loaded["spans"]
    if not spans:
        raise ValueError("the trace holds no bench.* span of the client")
    lo = min(s[1] for s in spans)
    hi = max(s[1] + s[2] for s in spans)
    chips = sorted({op[0] for op in loaded["ops"]})
    per_chip = {c: [] for c in chips}
    modules: dict = {}
    by_name: dict = {}
    for chip, name, start, dur, module, in_while in loaded["ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        per_chip[chip].append([a, b])
        m = modules.setdefault(module, {"all": {}, "while": {}})
        m["all"].setdefault(chip, []).append([a, b])
        if in_while:
            m["while"].setdefault(chip, []).append([a, b])
        by_name.setdefault(name, []).append([a, b])
    busy = {c: _union(v) for c, v in per_chip.items()}
    busy_s = (sum(_length(v) for v in busy.values()) / len(chips) / 1e9
              if chips else 0.0)
    any_busy = _union([iv for v in busy.values() for iv in v])

    steps = []
    for name, start, dur in spans:
        if name != "bench.step":
            continue
        inside = sum(_length(_clip(v, start, start + dur))
                     for v in busy.values()) / max(1, len(chips))
        steps.append([(start - lo) / 1e9, dur / 1e9, inside / 1e9])

    def client_in(t: float) -> str:
        for name, start, dur in spans:
            if start <= t < start + dur:
                return name[len(_SPAN_PREFIX):]
        return "between"

    gaps = []
    edge = lo
    for a, b in any_busy + [[hi, hi]]:
        if a > edge:
            gaps.append([client_in((edge + a) / 2), (a - edge) / 1e9])
        edge = max(edge, b)

    def mean_s(by_chip: dict) -> float:
        return sum(_length(_union(v)) for v in by_chip.values()) / len(
            chips) / 1e9

    out_modules = {module: {"busy_s": mean_s(m["all"]),
                            "while_s": mean_s(m["while"])}
                   for module, m in modules.items()}
    top_ops = sorted(([n, _length(v) / 1e9] for n, v in by_name.items()),
                     key=lambda t: -t[1])[:10]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s,
            "chips": len(chips), "modules": out_modules, "steps": steps,
            "top_ops": top_ops,
            "top_gaps": sorted(gaps, key=lambda t: -t[1])[:10]}


def step_device_ms(reduced: dict, config: dict):
    """Per traced step, the device milliseconds of the XLA modules that are
    the step (those whose name matches the configuration's
    `trace.step_modules`): {"all": ..., "while": inside a while body}; None
    where the trace holds no such module or no step."""
    pattern = config["trace"]["step_modules"]
    found = [m for name, m in reduced["modules"].items()
             if re.search(pattern, name)]
    n = len(reduced["steps"])
    busy = sum(m["busy_s"] for m in found)
    if not n or not busy:
        return None
    return {"all": 1e3 * busy / n,
            "while": 1e3 * sum(m["while_s"] for m in found) / n}
