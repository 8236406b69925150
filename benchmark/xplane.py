"""A profiler trace (xplane.pb) as plain tuples, and the reductions that the
harness itself reports: the device's busy time over the traced stretch
(`device.busy_s`, `device.window_s`) and the `breakdown`.  Every per-layer
metric's own reduction sits in its reader under layer_metrics/, which is
handed what load() returns; the helpers here (union, stretch, family) are
theirs to use.

  load(path)    every line of each TPU plane and every host event, by
                jax.profiler.ProfileData, plus each operation's
                `hlo_category` and `tf_op` (the JAX op path), which
                ProfileData does not expose and a forty-line reader of the
                protobuf wire format below does.

Times are nanoseconds on the trace's own clock.
"""
import collections
import gzip
import re
import statistics


# -- the protobuf wire format, as far as XSpace needs it ----------------------
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message; nested messages stay bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield key >> 3, value


def op_metadata(raw):
    """{plane name: {event name: {"hlo_category": str, "tf_op": str}}} from
    the bytes of an XSpace: XPlane.event_metadata (field 4) holds each
    operation's XStats (field 5) keyed through XPlane.stat_metadata (5)."""
    wanted = ("hlo_category", "tf_op")
    planes = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name, entries, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                entries.append(v)
            elif f == 5:
                meta = dict(_fields(dict(_fields(v))[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        ops = planes.setdefault(name, {})
        for entry in entries:
            meta = list(_fields(dict(_fields(entry))[2]))
            stats = {}
            for f, v in meta:
                if f != 5:
                    continue
                stat = dict(_fields(v))
                key = stat_names.get(stat.get(1))
                if key in wanted and 5 in stat:
                    stats[key] = bytes(stat[5]).decode()
            ops[bytes(dict(meta).get(2, b"")).decode()] = stats
    return planes


def load(path):
    """{"devices": {plane: {"lines": {line name: [(name, start, duration)]},
    "meta": {op name: {"hlo_category", "tf_op"}}}}, "host": [(name, start,
    duration)]}.  The device's lines are `XLA Modules` (one event per
    execution of a program), `XLA Ops`, `Async XLA Ops`, `Steps`."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    meta = op_metadata(raw)
    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events] for line in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            out["devices"][plane.name] = {"lines": lines,
                                          "meta": meta.get(plane.name, {})}
        elif plane.name == "/host:CPU":
            out["host"] = [e for events in lines.values() for e in events]
    return out


# -- helpers for the readers, and the harness's own reductions ----------------
def union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def first_device(trace):
    return trace["devices"][min(trace["devices"])] if trace["devices"] \
        else None


def stretch(dev):
    """(steps, ops) of one device: the [start, end] of every execution of
    the train step (the module that took most of the device's time), and
    the operations that ran between the first one's start and the last
    one's end.  The profiler's own start and stop are outside.  ([], [])
    where there is no device or no program ran."""
    total = collections.Counter()
    modules = dev["lines"].get("XLA Modules", []) if dev else []
    for name, _, duration in modules:
        total[name] += duration
    if not total:
        return [], []
    step_name = total.most_common(1)[0][0]
    steps = sorted([s, s + d] for n, s, d in modules if n == step_name)
    w0, w1 = steps[0][0], steps[-1][1]
    return steps, [(n, s, d) for n, s, d in dev["lines"].get("XLA Ops", [])
                   if s >= w0 and s + d <= w1]


def family(name, meta):
    """A stable, readable name for an operation: its HLO category and its JAX
    op path ("convolution_fusion:_jvp_...i_oi-_...o_/dot_general"), never
    the raw HLO text.  Without metadata, the instruction's name stem."""
    info = meta.get(name, {})
    path = re.sub(r"^jit\([^)]*\)/", "", info.get("tf_op", "")).rstrip(":")
    if info.get("hlo_category"):
        label = info["hlo_category"] + (":" + path if path else "")
    else:
        label = re.sub(r"[.\d]+$", "", name.split(" = ")[0].lstrip("%"))
    return re.sub(r"[^A-Za-z0-9_.:/-]", "_", label)


def busy(trace):
    """(busy_s, window_s): the union of the intervals in which an operation
    ran, and the traced stretch, each averaged over the devices that ran the
    step.  (0, 0) where no device did."""
    pairs = []
    for dev in trace["devices"].values():
        steps, ops = stretch(dev)
        if steps:
            pairs.append((sum(e - s for s, e in union(
                (s, s + d) for _, s, d in ops)),
                steps[-1][1] - steps[0][0]))
    if not pairs:
        return 0.0, 0.0
    return (statistics.mean(b for b, _ in pairs) / 1e9,
            statistics.mean(w for _, w in pairs) / 1e9)


def breakdown(trace, top=10):
    """The first device's traced stretch as the contract's `breakdown`:
    `device_ops`, the operation families with most device time, and
    `idle_gaps`, the idle time (the stretch's complement of the busy union)
    by whether it fell inside a step or between two and by the benchmark's
    own host annotation (bench/...) that was active then."""
    dev = first_device(trace)
    steps, ops = stretch(dev)
    families, gaps = collections.Counter(), collections.Counter()
    for n, _, d in ops:
        families[family(n, dev["meta"])] += d
    spans = [(n.split("/", 1)[1], s, s + d) for n, s, d in trace["host"]
             if n.startswith("bench/")]
    w0, w1 = steps[0][0], steps[-1][1]
    edges = [[w0, w0]] + union((s, s + d) for _, s, d in ops) + [[w1, w1]]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start <= end:
            continue
        mid = (end + start) / 2
        where = "in_step" if any(s <= mid <= e for s, e in steps) \
            else "between_steps"
        doing = next((n for n, s, e in spans if s <= mid <= e), "other")
        gaps[f"{where}/{doing}"] += start - end
    return {"device_ops": [[n, ns / 1e9] for n, ns in
                           families.most_common(top)],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps.most_common(top)]}
