"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction every PR's metrics go through, kept with the benchmark: busy
union and idle gaps of each device, the gaps named by what the host was doing,
time by operation (self time, since operations nest: a ``while`` holds its
body), kernels by name, collectives and how much of them no compute covers.

Pure functions over intervals, so they are tested without a chip; only
``load`` touches the file, through ``jax.profiler.ProfileData``.
Times are seconds unless a name ends in ``_ns``.
"""

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
ANCHOR = "bench_anchor"


def short(name):
    """An operation's event name is its whole HLO line; the instruction's own
    name is what comes before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def custom_call_results(name):
    """The result shapes of a ``custom-call`` event, e.g.
    ``['bf16[4,32,4096,128]', 'f32[4,32,4096,8]']``; None for other events."""
    if " custom-call(" not in name or " = " not in name:
        return None
    result = name.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    return re.findall(r"[a-z0-9]+\[[0-9,]*\]", result)


def load(path):
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}]"""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(str(path)).planes:
        lines = []
        for ln in p.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in ln.events]
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": p.name, "lines": lines})
    return planes


def device_ops(planes, line=OPS_LINE):
    """{device index: [(name, start_ns, dur_ns)] of its 'XLA Ops' line}"""
    out = {}
    for p in planes:
        m = DEVICE_PLANE.match(p["name"])
        if not m:
            continue
        evs = [e for ln in p["lines"] if ln["name"] == line
               for e in ln["events"]]
        out[int(m.group(1))] = sorted(evs, key=lambda e: (e[1], -e[2]))
    return out


def find_anchor(planes):
    """Start (ns, trace clock) of the harness's anchor annotation, or None."""
    for p in planes:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            for name, start, _ in ln["events"]:
                if name == ANCHOR:
                    return start
    return None


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def gaps(busy, lo, hi):
    """The complement of a disjoint sorted ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a, b):
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gaps(idle, spans, other="host, no span"):
    """Idle seconds by the host span that covers most of each gap.
    ``spans``: [(name, t0, t1)] on the gaps' clock. Innermost (shortest)
    covering span wins a tie."""
    out = defaultdict(float)
    for g in idle:
        best, best_cover, best_len = other, 0.0, float("inf")
        for name, t0, t1 in spans:
            c = overlap(g, (t0, t1))
            if c > best_cover + 1e-12 or (
                    c > 0 and abs(c - best_cover) <= 1e-12 and t1 - t0 < best_len):
                best, best_cover, best_len = name, c, t1 - t0
        out[best] += g[1] - g[0]
    return dict(out)


def self_times(events):
    """{name: self seconds}: an event's duration less what events nested in
    it (on the same line) take. Events: (name, start_ns, dur_ns), sorted by
    (start, -duration)."""
    out = defaultdict(float)
    stack = []  # (name, end, child_ns)

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            dur = end - start
            out[name] += max(dur - child, 0.0) / 1e9
            if stack:
                top = stack[-1]
                stack[-1] = (top[0], top[1], top[2], top[3] + dur)

    for name, start, dur in events:
        close(start)
        stack.append((name, start + dur, start, 0.0))
    close(float("inf"))
    return dict(out)


def kernel_time(events, pattern):
    """(seconds, count) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hit = [d for n, _, d in events if rx.search(n)]
    return sum(hit) / 1e9, len(hit)


def exposed_collectives(events, async_events, lo, hi):
    """(exposed, in flight) seconds inside [lo, hi] (ns). A collective is in
    flight from its start to its done (the 'Async XLA Ops' line) or while its
    own operation runs (the 'XLA Ops' line); it is exposed where no other
    operation computes on that device. Operations that merely enclose others
    (a ``while``, a call) are not compute: only leaves count."""
    leaves = leaf_events(events)
    is_coll = lambda n: bool(COLLECTIVE.search(short(n)))
    coll = union(clip(
        [(s, s + d) for n, s, d in leaves if is_coll(n)]
        + [(s, s + d) for n, s, d in async_events if is_coll(n)], lo, hi))
    comp = union(clip([(s, s + d) for n, s, d in leaves
                       if not is_coll(n)], lo, hi))
    covered, j = 0.0, 0
    for c in coll:  # both lists are sorted and disjoint
        while j < len(comp) and comp[j][1] <= c[0]:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < c[1]:
            covered += overlap(c, comp[k])
            k += 1
    return (total(coll) - covered) / 1e9, total(coll) / 1e9


def leaf_events(events):
    """Events that hold no other event of the line."""
    out = []
    evs = list(events)
    for i, (n, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= s + d:
            out.append((n, s, d))
    return out


def reduce(path, anchor_mono_ns, t0_mono, t1_mono, host_spans, chips):
    """The summary the per-layer readers see.

    ``anchor_mono_ns``: the host's monotonic clock when the anchor annotation
    was written; ``t0_mono``/``t1_mono``: the traced window on that clock (s);
    ``host_spans``: [(name, t0, t1)] on that clock (s)."""
    planes = load(path)
    anchor = find_anchor(planes)
    if anchor is None:
        raise ValueError("trace holds no anchor annotation")
    shift = anchor - anchor_mono_ns  # trace ns = mono ns + shift
    lo, hi = t0_mono * 1e9 + shift, t1_mono * 1e9 + shift
    ops = device_ops(planes)
    flights = device_ops(planes, "Async XLA Ops")
    devs = sorted(ops)[:chips]
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    busy = {d: union(clip([(s, s + du) for _, s, du in ops[d]], lo, hi))
            for d in devs}
    first = devs[0]
    spans_ns = [(n, a * 1e9 + shift, b * 1e9 + shift) for n, a, b in host_spans]
    idle = gaps(busy[first], lo, hi)
    named = name_gaps(idle, spans_ns)
    in_win = {d: [e for e in ops[d] if e[1] + e[2] > lo and e[1] < hi]
              for d in devs}
    selfs = defaultdict(float)
    for name, secs in self_times(in_win[first]).items():
        selfs[short(name)] += secs
    exposed = [exposed_collectives(in_win[d], flights.get(d, []), lo, hi)
               for d in devs]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(total(b) for b in busy.values()) / len(devs) / 1e9,
        "busy_s_by_device": {d: total(b) / 1e9 for d, b in busy.items()},
        "device_ops": sorted(selfs.items(), key=lambda kv: -kv[1]),
        "idle_gaps": sorted(
            ((n, s / 1e9) for n, s in named.items()), key=lambda kv: -kv[1]),
        "events": in_win,
        "exposed_collective_s": sum(e[0] for e in exposed) / len(devs),
        "collective_s": sum(e[1] for e in exposed) / len(devs),
    }


def describe(path, top=40):
    """A by-hand look at a trace: planes, lines, and the names that take the
    most time on each device line."""
    lines = []
    for p in load(path):
        lines.append(f"PLANE {p['name']}")
        for ln in p["lines"]:
            evs = ln["events"]
            lines.append(f"  LINE {ln['name']}: {len(evs)} events")
            if DEVICE_PLANE.match(p["name"]) or ln["name"].startswith("python"):
                by = defaultdict(lambda: [0.0, 0])
                for n, _, d in evs:
                    by[n][0] += d
                    by[n][1] += 1
                for n, (d, c) in sorted(by.items(), key=lambda kv: -kv[1][0])[:top]:
                    lines.append(f"      {d / 1e6:10.3f} ms x{c:<6} {n[:150]}")
    return "\n".join(lines)
