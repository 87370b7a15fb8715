"""Reduction of a profiler trace to numbers: device busy union, idle
share, device time by operation name, and idle gaps named by what the
host was doing. The trace is first flattened to plain events
({"plane", "line", "name", "start_ns", "dur_ns", "text"}), so the
reduction can be checked on a small recorded trace
(tests/data/small_trace.json) with no profiler at hand."""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."          # the benchmark's own TraceAnnotation names


def flatten_xplane(trace_dir):
    """Read the newest .xplane.pb under ``trace_dir`` with JAX alone. Keeps
    the device planes' operation lines and, from the host, only the
    benchmark's own annotations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    events = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(HOST_PREFIX):
                    continue
                text = ""
                if dev:
                    text = " ".join(str(v) for k, v in ev.stats
                                    if isinstance(v, str))[:400]
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name, "start_ns": ev.start_ns,
                               "dur_ns": ev.duration_ns, "text": text})
    return events


def union(intervals):
    """Merge [start, end) intervals; -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a, b):
    """Total overlap of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def short_name(hlo):
    """An HLO line cut to what tells operations apart: its name, what it
    is, and the shape it gives (``%fusion.3 fusion bf16[2,4096]``)."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:96]
    shape = rest.lstrip("(").split("{")[0]
    kind = "custom-call" if "custom-call(" in rest else \
        rest.split("(")[0].split(" ")[-1] if rest[0] != "(" else \
        rest.split(") ")[-1].split("(")[0] if ") " in rest else ""
    tgt = ""
    if 'custom_call_target="' in rest:
        tgt = " " + rest.split('custom_call_target="')[1].split('"')[0]
    return f"{name} {kind}{tgt} {shape}"[:96]


class Reduced:
    """What the per-layer readers see of a trace."""

    def __init__(self, events, window=None, host_spans=None):
        """``window`` (start_ns, end_ns) in the trace's clock; default: the
        ``bench.window`` annotation, else first to last device event.
        ``host_spans`` {name: [(start_ns, end_ns)]} adds spans taken on the
        host's clock by the harness (builds), already in the trace's clock."""
        dev = [e for e in events if e["plane"].startswith(DEVICE_PLANE)]
        host = [e for e in events if not e["plane"].startswith(DEVICE_PLANE)]
        if window is None:
            w = [e for e in host if e["name"] == HOST_PREFIX + "window"]
            if w:
                window = (w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"])
            elif dev:
                window = (min(e["start_ns"] for e in dev),
                          max(e["start_ns"] + e["dur_ns"] for e in dev))
            else:
                window = (0, 0)
        self.window = window
        lo, hi = window
        self.window_s = (hi - lo) / 1e9
        self.planes = sorted({e["plane"] for e in dev})
        self.events = dev
        per_plane = []
        self._busy = {}
        for p in self.planes:
            iv = _clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                        for e in dev if e["plane"] == p], lo, hi)
            self._busy[p] = union(iv)
            per_plane.append(sum(e - s for s, e in self._busy[p]) / 1e9)
        # averaged over the chips used
        self.busy_s = sum(per_plane) / len(per_plane) if per_plane else 0.0
        self.host = {}
        for e in host:
            self.host.setdefault(e["name"][len(HOST_PREFIX):], []).append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"]))
        for k, v in (host_spans or {}).items():
            self.host.setdefault(k, []).extend(v)

    @property
    def idle_share(self):
        if self.window_s <= 0 or not self.planes:
            return None
        return 1.0 - self.busy_s / self.window_s

    def _in_window(self, e):
        lo, hi = self.window
        return e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo

    def op_seconds(self):
        """{short name: seconds} of device operations in the window, summed
        over chips. Operations that only wrap others (a while loop's body
        runs as events inside it) are skipped where a later event of the
        plane starts inside them, so time is not counted twice."""
        out = {}
        for p in self.planes:
            evs = sorted((e for e in self.events
                          if e["plane"] == p and self._in_window(e)),
                         key=lambda e: (e["start_ns"], -e["dur_ns"]))
            for i, e in enumerate(evs):
                if i + 1 < len(evs) and \
                        evs[i + 1]["start_ns"] < e["start_ns"] + e["dur_ns"]:
                    continue
                k = short_name(e["name"])
                out[k] = out.get(k, 0.0) + e["dur_ns"] / 1e9
        return out

    def kernel_calls(self, any_of=(), all_of=(), regex=None):
        """(seconds, calls) of the device events in the window whose name
        holds every string of ``all_of``, one of ``any_of`` if given, and
        matches ``regex`` if given.
        On a TPU an event's name is the operation's whole HLO text, so a
        kernel that the program gave no name is found by its custom-call
        target and operand shapes."""
        n = 0
        tot = 0.0
        rx = re.compile(regex) if regex else None
        for e in self.events:
            txt = e["name"] + " " + e["text"]
            if self._in_window(e) and all(q in txt for q in all_of) and (
                    not any_of or any(q in txt for q in any_of)) and (
                    rx is None or rx.search(txt)):
                n += 1
                tot += e["dur_ns"] / 1e9
        return tot, n

    def gaps(self):
        """Idle gaps of the first chip inside the window, longest first:
        [(seconds, what the host was doing)]. The name is the host span
        kind that covers most of the gap, in the order given by
        ``GAP_KINDS``; "other" where none covers a tenth of it."""
        if not self.planes:
            return []
        lo, hi = self.window
        busy = self._busy[self.planes[0]]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        spans = {k: union(v) for k, v in self.host.items()}
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            best, cover = "other", 0.1 * (b - a)
            for kind in GAP_KINDS:
                c = _overlap([[a, b]], spans.get(kind, []))
                if c > cover:
                    best, cover = kind, c
                    break
            out.append(((b - a) / 1e9, best))
        return sorted(out, reverse=True)

    def breakdown(self, top=10):
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = self.gaps()
        by_kind = {}
        for s, k in gaps:
            by_kind[k] = by_kind.get(k, 0.0) + s
        rows = [[f"all:{k}", s] for k, s in
                sorted(by_kind.items(), key=lambda kv: -kv[1])]
        rows += [[f"longest:{k}", s] for s, k in gaps[:max(0, top - len(rows))]]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": rows[:top]}


# what the host may have been doing in a gap, the first that covers it wins
GAP_KINDS = ("compiling", "feed", "sched_step", "wait_request", "window")
