"""Where the device's time and its idle gaps go, by the program's names.

``bench/tracefile.py`` reads a trace the way the benchmark's metrics do:
busy time, per-op time, and each idle gap charged to the ``bench.*``
span that covers most of it. This module adds what the program's own
instrumentation lets a trace say:

- ``load(path)`` reads a JAX profiler ``.xplane.pb`` into events
  ``(plane, line, name, start_ns, dur_ns, scope, module)``: the host
  spans named ``bench.*`` and ``serve.*`` (the engine's phases,
  ``Telemetry.phase``), and each device op with its HLO module (the
  ``XLA Modules`` event it runs in) and its scope path: the op's
  ``op_name`` metadata, which ``jax.named_scope`` writes, read from the
  module's HLO that the profiler keeps in the trace (``hlo_op_names``).
  ``tracefile.load``'s 5-tuples reduce too, without scopes.
- ``reduce(events)`` is ``tracefile.reduce`` of the same device ops and
  window (busy time, idle share and per-op time read the same, to the
  bit), with ``idle_gaps`` replaced and these entries added:
  - ``idle_gaps``: each idle gap charged to the innermost host span that
    covers it, split where the innermost span changes. Host spans nest
    (``serve.emit`` inside ``serve.step`` inside ``bench.step``); the
    innermost is the open span that began last.
  - ``scope_seconds``: device seconds by top-level scope (the outermost
    of ``SCOPES`` on the op's scope path, else ``(unscoped)``), and
    ``unscoped_op_seconds``, the per-op seconds of the unscoped ops.
  - ``module_seconds``: device seconds by HLO module.
- ``shares(red, pool_shapes)``: the KV pool's, the weight packing's and
  the unscoped share of busy time.
"""

from __future__ import annotations

import bisect
import collections
import heapq
import re

from bench import tracefile

# the program's device scopes (jax.named_scope in models/, serving/, kernels/)
SCOPES = ("kv_pool", "attn", "ffn_pack")
UNSCOPED = "(unscoped)"
NO_SPAN = "(no annotation)"
HOST_PREFIXES = ("bench.", "serve.")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
OP_NAME = re.compile(r"^%(\S+) =")
RESULT_SHAPE = re.compile(r"^%\S+ = \w+\[([\d,]*)\]")


def load(path: str) -> list[tuple]:
    """Host spans and device ops of one trace, with scope and module."""
    from jax.profiler import ProfileData
    out, modules = [], collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            if host:
                out.extend((plane.name, line.name, e.name, int(e.start_ns),
                            int(e.duration_ns), "", "")
                           for e in line.events
                           if e.name.startswith(HOST_PREFIXES))
            elif line.name == MODULES_LINE:
                modules[plane.name].extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name) for e in line.events)
            elif line.name == tracefile.DEVICE_OPS_LINE:
                out.extend((plane.name, line.name, e.name, int(e.start_ns),
                            int(e.duration_ns), "", "")
                           for e in line.events)
    # each op takes the module it runs inside, and its op_name there
    names = hlo_op_names(path)
    for plane, mods in modules.items():
        mods.sort()
        starts = [s for s, _, _ in mods]
        for i, ev in enumerate(out):
            if ev[0] != plane:
                continue
            j = bisect.bisect_right(starts, ev[3]) - 1
            if j < 0 or ev[3] >= mods[j][1]:
                continue
            module = mods[j][2]
            op = OP_NAME.match(ev[2])
            scope = names.get(module, {}).get(op.group(1), "") if op else ""
            out[i] = ev[:5] + (scope, module)
    return out


# -- the HLO the profiler keeps: a protobuf read field by field ------------

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of one protobuf message: an int for varint
    and fixed fields, a memoryview for length-delimited ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = int.from_bytes(b[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _field(b, number):
    for f, v in _fields(b):
        if f == number:
            yield v


def _text(v) -> str:
    return bytes(v).decode(errors="replace")


def hlo_op_names(path: str) -> dict:
    """{module: {instruction: op_name}} from the HLO of each module that the
    profiler keeps in its metadata plane (XSpace.planes=1, XPlane name=2,
    event_metadata=4 -> XEventMetadata name=2, stats=5 -> XStat
    metadata_id=1, bytes=6; stat_metadata=5 -> XStatMetadata id=1,
    name=2; the HloProto's hlo_module=1 -> computations=3 ->
    instructions=2 -> name=1, metadata=7 -> op_name=2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _field(space, 1):
        if _text(next(_field(plane, 2), b"")) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in _field(plane, 5):
            for meta in _field(entry, 2):
                sid = next(_field(meta, 1), 0)
                stat_names[sid] = _text(next(_field(meta, 2), b""))
        for entry in _field(plane, 4):
            for meta in _field(entry, 2):
                module = _text(next(_field(meta, 2), b""))
                for stat in _field(meta, 5):
                    sid = next(_field(stat, 1), 0)
                    if stat_names.get(sid) != HLO_PROTO_STAT:
                        continue
                    for proto in _field(stat, 6):
                        out[module] = _instruction_op_names(proto)
    return out


def _instruction_op_names(hlo_proto) -> dict:
    names = {}
    for mod in _field(hlo_proto, 1):
        for comp in _field(mod, 3):
            for ins in _field(comp, 2):
                name = _text(next(_field(ins, 1), b""))
                for md in _field(ins, 7):
                    names[name] = _text(next(_field(md, 2), b""))
    return names


def top_scope(path: str) -> str:
    """The outermost of SCOPES on a scope path, else UNSCOPED."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def _innermost_segments(spans, lo, hi):
    """[(start, end, label)] covering [lo, hi]: in each, the innermost open
    host span (latest start, then earliest end), else NO_SPAN."""
    edges = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                               if lo < t < hi})
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heap, k, out = [], 0, []
    for a, b in zip(edges, edges[1:]):
        while k < len(by_start) and spans[by_start[k]][1] <= a:
            i = by_start[k]
            heapq.heappush(heap, (-spans[i][1], spans[i][2], i))
            k += 1
        while heap and heap[0][1] <= a:          # innermost has ended
            heapq.heappop(heap)
        # an ended span deeper in the heap surfaces later and is dropped
        # then; the top is open over all of [a, b)
        label = spans[heap[0][2]][0] if heap else NO_SPAN
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def _charge(gaps, segments, idle, scale):
    """Add each gap's overlap with each labelled segment to idle."""
    j = 0
    for gs, ge in gaps:
        while j < len(segments) and segments[j][1] <= gs:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < ge:
            s, e, label = segments[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                idle[label] += ov * scale
            k += 1


def reduce(events, *, top: int = 10) -> dict:
    """``tracefile.reduce`` with idle charged to the innermost host span,
    and device seconds by scope and by module (see the module's doc)."""
    ev7 = [tuple(e) + ("", "")[:7 - len(e)] for e in events]
    host = [(n, s, s + d) for p, _, n, s, d, _, _ in ev7
            if p.startswith("/host:") and n != tracefile.WINDOW]
    # the benchmark's reading of the same device ops and window
    red = tracefile.reduce(
        [e[:5] for e in ev7 if not e[0].startswith("/host:")
         or e[2] == tracefile.WINDOW], top=top)
    win = [(s, s + d) for p, _, n, s, d, _, _ in ev7
           if p.startswith("/host:") and n == tracefile.WINDOW]
    lo, hi = win[0]
    per_dev = collections.defaultdict(list)
    for p, line, n, s, d, scope, module in ev7:
        if p.startswith("/device:") and line == tracefile.DEVICE_OPS_LINE:
            per_dev[p].append((n, s, s + d, scope, module))
    nd = len(per_dev)
    segments = _innermost_segments(host, lo, hi)
    idle: dict = collections.defaultdict(float)
    scopes: dict = collections.defaultdict(float)
    unscoped: dict = collections.defaultdict(float)
    modules: dict = collections.defaultdict(float)
    for ops in per_dev.values():
        for n, s, e, scope, module in ops:
            if tracefile.CONTROL_FLOW.match(n):
                continue
            for cs, ce in tracefile._clip([(s, e)], lo, hi):
                sec = (ce - cs) / 1e9 / nd
                top_s = top_scope(scope)
                scopes[top_s] += sec
                if top_s == UNSCOPED:
                    unscoped[n[:tracefile.NAME_CHARS]] += sec
                modules[module or "(unknown)"] += sec
        merged = tracefile._union(tracefile._clip(
            [(s, e) for _, s, e, _, _ in ops], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        _charge(gaps, segments, idle, 1 / 1e9 / nd)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    red["idle_gaps"] = [[k, v] for k, v in rank(idle)]
    red["scope_seconds"] = dict(scopes)
    red["unscoped_op_seconds"] = dict(unscoped)
    red["module_seconds"] = dict(modules)
    return red


def result_shape(op_name: str) -> tuple | None:
    """The result dims of an op named by its HLO text, or None (tuples)."""
    m = RESULT_SHAPE.match(op_name)
    if m is None:
        return None
    return tuple(int(x) for x in m.group(1).split(",") if x)


def pool_op_seconds(red: dict, pool_shapes) -> float:
    """Device seconds of the ops whose result has a KV pool leaf's
    stacked (layers, B, T, ...) or one-layer shape: the decode scan's
    slices of the pool, its relayout copies and its write-back, which no
    scope inside the layer can wrap. The op names carry no scope, so an
    attention op of such a shape would count too."""
    shapes = set()
    for sh in pool_shapes:
        sh = tuple(sh)
        shapes |= {sh, sh[1:], (1,) + sh[1:]}
    return sum(v for k, v in red["op_seconds"].items()
               if result_shape(k) in shapes)


def shares(red: dict, pool_shapes=()) -> dict:
    """Shares of busy time, in %: ``kv_pool_share`` (the ``kv_pool`` scope,
    plus the unscoped ops of a pool leaf's shape), ``weight_pack_share``
    (the ``ffn_pack`` scope), ``unscoped_share``; and
    ``pool_shape_share``, the ops of a pool leaf's shape whatever their
    scope (the count by hand that the scope should agree with)."""
    busy = red["busy_s"]
    sc = red["scope_seconds"]
    pool_shaped = pool_op_seconds(red, pool_shapes)
    pool_unscoped = pool_op_seconds(
        {"op_seconds": red["unscoped_op_seconds"]}, pool_shapes)
    return {
        "kv_pool_share": 100.0 * (sc.get("kv_pool", 0.0) + pool_unscoped)
        / busy,
        "weight_pack_share": 100.0 * sc.get("ffn_pack", 0.0) / busy,
        "unscoped_share": 100.0 * sc.get(UNSCOPED, 0.0) / busy,
        "pool_shape_share": 100.0 * pool_shaped / busy,
    }
