"""Device time by `jax.named_scope`, from the profiler's `.xplane.pb`.

An XLA operation's trace event is named by its HLO text, which carries no
scope; the name stack the program traced it under (`jit(step)/jit(main)/
sparse_select/top_k`) sits in the event's METADATA, which
`jax.profiler.ProfileData` does not hand out. So this reads the file's
protobuf wire format itself, and only what it needs of it (fields as in
tsl/profiler/protobuf/xplane.proto):

  XSpace.planes = 1;  XPlane: name = 2, lines = 3, event_metadata = 4
  (map<int64, XEventMetadata>), stat_metadata = 5 (map<int64,
  XStatMetadata>);  XLine: name = 2 (display_name = 11), timestamp_ns = 3,
  events = 4;  XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3;
  XEventMetadata: id = 1, name = 2, stats = 5;  XStat: metadata_id = 1,
  str_value = 5, ref_value = 7 (a stat_metadata id whose NAME is the
  string);  XStatMetadata: id = 1, name = 2.

`of(rec)` gives, for the newest trace of this run, the self seconds of
device 0's operations inside the traced window by step program and by the
FIRST of the given scopes found in the operation's name stack. None
without a device plane.
"""
import bisect
import collections

from harness import span_reduce, trace_reduce

DEVICE0 = "/device:TPU:0"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """[(field number, wire type, value)] of one message: an int for
    varints and fixed widths, a memoryview for length-delimited ones."""
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        out.append((num, wt, val))
    return out


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entries(msg_fields, number):
    """{key: value message view} of a map<int64, message> field."""
    out = {}
    for num, wt, val in msg_fields:
        if num == number and wt == 2:
            entry = {n: v for n, _, v in fields(val)}
            if 1 in entry and 2 in entry:
                out[entry[1]] = entry[2]
    return out


def read_device_ops(path):
    """[(name stack or "", hlo name, start_s, end_s)] of device 0's `XLA
    Ops` line."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, wt, plane in fields(space):
        if num != 1 or wt != 2:
            continue
        pf = fields(plane)
        name = next((_text(v) for n, w, v in pf if n == 2 and w == 2), "")
        if name != DEVICE0:
            continue
        stat_names = {k: _text(next((v for n, w, v in fields(m)
                                     if n == 2 and w == 2), b""))
                      for k, m in _map_entries(pf, 5).items()}
        stacks, hlo = {}, {}
        for k, m in _map_entries(pf, 4).items():
            mf = fields(m)
            hlo[k] = _text(next((v for n, w, v in mf
                                 if n == 2 and w == 2), b""))
            found = ""
            for n, w, stat in mf:
                if n != 5 or w != 2:
                    continue
                sf = {a: c for a, _, c in fields(stat)}
                text = (_text(sf[5]) if 5 in sf
                        else stat_names.get(sf.get(7), ""))
                if "jit(" in text and len(text) > len(found):
                    found = text
            stacks[k] = found
        out = []
        for n, w, line in pf:
            if n != 3 or w != 2:
                continue
            lf = fields(line)
            lname = next((_text(v) for a, b, v in lf
                          if a in (2, 11) and b == 2), "")
            if lname != trace_reduce.OPS_LINE:
                continue
            t0 = next((v for a, b, v in lf if a == 3 and b == 0), 0) * 1e-9
            for a, b, ev in lf:
                if a != 4 or b != 2:
                    continue
                ef = {x: z for x, _, z in fields(ev)}
                start = t0 + ef.get(2, 0) * 1e-12
                out.append((stacks.get(ef.get(1), ""),
                            trace_reduce.op_name(hlo.get(ef.get(1), "")),
                            start, start + ef.get(3, 0) * 1e-12))
        return out
    return None


def reduce_file(path, scopes):
    """{"runs": {module: executions}, "seconds": {module: {scope: self
    seconds}}}: each operation's self time goes to the first of `scopes`
    its name stack contains, else to "(other)"."""
    ops = read_device_ops(path)
    devices, bench = trace_reduce.read_planes(path)
    if not ops or not devices or not bench:
        return None
    lo, hi = bench[0][1], max(e for _, _, e in bench)
    mods = sorted((s, e, trace_reduce.module_name(n))
                  for n, s, e in devices[min(devices)].get(
                      trace_reduce.MODULES_LINE, []) if s >= lo and e <= hi)
    starts = [m[0] for m in mods]
    runs = collections.Counter(m[2] for m in mods)
    seconds = collections.defaultdict(collections.Counter)
    tagged = [((stack, s), s, e) for stack, _, s, e in ops
              if s >= lo and e <= hi]
    for (stack, start), own in trace_reduce.self_times(tagged):
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start > mods[i][1]:
            continue
        parts = stack.rstrip(":").split("/")
        scope = next((sc for sc in scopes if sc in parts), "(other)")
        seconds[mods[i][2]][scope] += own
    return {"runs": dict(runs),
            "seconds": {m: dict(c) for m, c in seconds.items()}}


def of(rec, scopes):
    tr = rec.get("trace")
    if not tr:
        return None
    key = "scope_times:" + ",".join(scopes)
    if key not in tr:
        path = span_reduce.newest_trace()
        tr[key] = reduce_file(path, scopes) if path else None
    return tr[key]
