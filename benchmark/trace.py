"""From a profiler trace to numbers. Two parts:

* a thin reader of the `.xplane.pb` that `jax.profiler` writes
  (`jax.profiler.ProfileData.from_file`), which returns plain lists of
  `Event(name, start_ns, dur_ns, detail)` per device line;
* pure functions over such lists: union of intervals, time by name,
  executions of a module, the longest gaps. These are what the tests in
  `benchmark/tests/` exercise on synthetic lists.

What a v5e trace looks like (two looked at by hand, PR 24): one plane
per chip named `/device:TPU:<n>`, with the lines `Steps`, `XLA Modules`
(one event per execution of a compiled program, `jit_fused(<id>)`),
`XLA Ops` (one event per HLO operation that ran on the core, named by
the whole instruction text, `%paged_attention_v1.61 = bf16[...]
custom-call(...)`: a Pallas kernel keeps the `name=` its `pallas_call`
was given) and `Async XLA Ops` (copy-start/slice-start DMAs that overlap
the core's work; not counted as busy). Events carry no string stats.

`python3 benchmark/trace.py <file.xplane.pb>` prints the planes, lines
and heaviest names of a trace, for reading one by hand.
"""

import collections
import glob
import os
import re
import sys

Event = collections.namedtuple("Event", "name start_ns dur_ns detail")

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# ---------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------

def find_xplane(trace_dir):
    """The newest .xplane.pb under a jax.profiler trace directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def split_instruction(text):
    """('paged_attention_v1.61', 'bf16[...] custom-call(...)') from the
    HLO instruction text an `XLA Ops` event is named by, '%name = rest'.
    Only the name identifies the operation: the rest lists its operands,
    so a consumer of a kernel's output holds the kernel's name there."""
    name, _, rest = text.partition(" = ")
    return name.lstrip("%"), rest


ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
            "f8e4m3fn": 1, "f8e5m2": 1}


def result_itemsize(detail):
    """Bytes per element of an operation's (first) result, from the
    instruction text after ' = ': '(f32[256,512,64]{2,1,0}, ...' -> 4.
    None where the type is not in the table."""
    m = re.match(r"\(?\s*([a-z]+[0-9]+[a-z0-9]*)\[", detail)
    return ITEMSIZE.get(m.group(1)) if m else None


def _event(ev):
    name, rest = split_instruction(ev.name)
    return Event(name, int(ev.start_ns), int(ev.duration_ns), rest)


def read_device_lines(path, chips=None):
    """{plane name: {line name: [Event, ...]}} for the device planes of
    the trace (the first `chips` of them, by name)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [_event(ev) for ev in line.events]
        out[plane.name] = lines
    names = sorted(out)
    if chips is not None:
        names = names[:chips]
    return {n: out[n] for n in names}


# ---------------------------------------------------------------------
# pure functions over lists of events
# ---------------------------------------------------------------------

def clip(events, t0_ns, t1_ns):
    """Events cut to [t0, t1]; those outside are dropped."""
    out = []
    for e in events:
        a, b = max(e.start_ns, t0_ns), min(e.start_ns + e.dur_ns, t1_ns)
        if b > a:
            out.append(Event(e.name, a, b - a, e.detail))
    return out


def union_ns(events):
    """Total time covered by at least one event: the union of the
    intervals, so that nested or overlapping events count once."""
    total, end = 0, None
    for a, d in sorted((e.start_ns, e.dur_ns) for e in events):
        b = a + d
        if end is None or a > end:
            total += d
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def matching(events, needles):
    """Events whose own name holds any of the needles (never the detail,
    which names the operands)."""
    return [e for e in events if any(n in e.name for n in needles)]


def leaf_events(events):
    """Events that hold no other event: on a line where a `while` or a
    `call` wraps the operations of its body, only the leaves are work,
    and summing parents too would count the time twice."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    leaves = []
    for i, e in enumerate(evs):
        end = e.start_ns + e.dur_ns
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start_ns < end \
                and nxt.start_ns + nxt.dur_ns <= end and e.dur_ns > 0 \
                and (nxt.start_ns, nxt.dur_ns) != (e.start_ns, e.dur_ns):
            continue        # e contains the next event: a parent
        leaves.append(e)
    return leaves


def time_by_name(events, strip_suffix=True):
    """[(name, total_ns)] heaviest first. With strip_suffix the HLO
    instruction number goes (`fusion.12` -> `fusion`), so that the
    forty-eight layers' copies of one operation add up."""
    acc = collections.Counter()
    for e in events:
        name = e.name
        if strip_suffix:
            head, _, tail = name.rpartition(".")
            if head and tail.isdigit():
                name = head
        acc[name] += e.dur_ns
    return acc.most_common()


def module_executions(module_events, needle):
    """Durations (ns) of the executions of the compiled program whose
    name holds `needle`, in time order."""
    return [e.dur_ns for e in sorted(module_events,
                                     key=lambda e: e.start_ns)
            if needle in e.name]


def heaviest_module(module_events):
    """The name (instruction number and id stripped) of the compiled
    program with most device time, or None."""
    acc = collections.Counter()
    for e in module_events:
        acc[e.name.split("(")[0]] += e.dur_ns
    return acc.most_common(1)[0][0] if acc else None


def longest_gaps(events, t0_ns, t1_ns, top=10):
    """[(start_ns, dur_ns)] of the longest idle gaps between device
    events inside [t0, t1]."""
    gaps, end = [], t0_ns
    for a, d in sorted((e.start_ns, e.dur_ns)
                       for e in clip(events, t0_ns, t1_ns)):
        if a > end:
            gaps.append((end, a - end))
        end = max(end, a + d)
    if t1_ns > end:
        gaps.append((end, t1_ns - end))
    return sorted(gaps, key=lambda g: -g[1])[:top]


class DeviceTrace:
    """The device side of one traced window, reduced once and handed to
    every per-layer reader. Times in seconds, averaged over the chips
    used."""

    def __init__(self, planes):
        self.planes = planes            # {plane: {line: [Event]}}
        # leaf operations of every chip, one list per chip, found once:
        # every reader asks, and a window holds some 1e5 events
        self._all = [p.get(OPS_LINE, []) for p in planes.values()]
        self._ops = [leaf_events(line) for line in self._all]
        starts = [e.start_ns for line in self._ops for e in line]
        ends = [e.start_ns + e.dur_ns for line in self._ops for e in line]
        if not starts:
            raise ValueError("the trace holds no device operation")
        self.t0_ns, self.t1_ns = min(starts), max(ends)
        self.n_chips = len(planes)
        # busy: the union of ALL operations, parents included (a `while`
        # is on the core between its body's operations too); shares and
        # sums by name use the leaves, so that no time counts twice
        self._busy_s = sum(union_ns(p.get(OPS_LINE, []))
                           for p in planes.values()) / self.n_chips / 1e9

    def busy_s(self):
        return self._busy_s

    def window_s(self):
        return (self.t1_ns - self.t0_ns) / 1e9

    def kernel_s(self, needles):
        """Summed device time of the operations that match, per chip.
        A kernel is matched among ALL operations: it wraps no other
        work, but a DMA's `-done` event can fall inside its interval,
        and the leaf filter would then drop the kernel as a parent (it
        dropped 57 to 89 of 480 `flash_dq` calls: my chip runs PR 24)."""
        return sum(e.dur_ns for line in self._all
                   for e in matching(line, needles)) / self.n_chips / 1e9

    def kernel_calls(self, needles):
        return sum(len(matching(line, needles)) for line in self._all) \
            / self.n_chips

    def kernel_itemsize(self, needles):
        """Bytes per element of what the matching operations return (a
        kernel computes in the type of its operands and result); None
        where none ran or the type is unknown."""
        for line in self._all:
            for e in matching(line, needles):
                return result_itemsize(e.detail)
        return None

    def kernel_share_pct(self, needles):
        """The matching operations' share of the busy time, in percent;
        None where none ran."""
        t = self.kernel_s(needles)
        return 100.0 * t / self._busy_s if t > 0 else None

    def heaviest_module_ms_p50(self):
        """Median device time, in ms, of one execution of the compiled
        program that took most device time on the first chip; None where
        the modules line is empty."""
        modules = next(iter(self.planes.values())).get(MODULES_LINE, [])
        name = heaviest_module(modules)
        if name is None:
            return None
        durs = sorted(module_executions(modules, name))
        mid = len(durs) // 2
        med = durs[mid] if len(durs) % 2 else (durs[mid - 1] + durs[mid]) / 2
        return med / 1e6

    def breakdown(self, top=10):
        acc = collections.Counter()
        for line in self._ops:
            for name, ns in time_by_name(line):
                acc[name] += ns
        device_ops = [[n, ns / self.n_chips / 1e9]
                      for n, ns in acc.most_common(top)]
        first = next(iter(self.planes.values())).get(OPS_LINE, [])
        # the program writes no host span into the profiler's trace yet,
        # so a gap can be placed in time but not attributed
        idle = [[f"unattributed at +{(s - self.t0_ns) / 1e9:.4f}s", d / 1e9]
                for s, d in longest_gaps(first, self.t0_ns, self.t1_ns,
                                         top)]
        return {"device_ops": device_ops, "idle_gaps": idle}


def _dump(path, top=40):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if not plane.name.startswith(DEVICE_PLANE_PREFIX):
                continue
            events = [_event(e) for e in evs]
            for e in events[:3]:
                print(f"    first: {e.name!r} dur {e.dur_ns} ns "
                      f"= {e.detail[:120]!r}")
            for name, ns in time_by_name(events)[:top]:
                print(f"    {ns / 1e6:12.3f} ms  {name}")
            print(f"    union {union_ns(events) / 1e6:.3f} ms, leaves "
                  f"{union_ns(leaf_events(events)) / 1e6:.3f} ms of "
                  f"{len(leaf_events(events))} events")


if __name__ == "__main__":
    target = sys.argv[1]
    _dump(find_xplane(target) if os.path.isdir(target) else target)
