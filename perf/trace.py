"""Device time per program layer, read from a profiler trace.

Two inputs: the compiled segment's HLO module, whose instructions each
carry the Python stack that emitted them (``stack_frame_id`` into the
module's stack-frame index), and the device trace of the traced window,
whose "XLA Ops" events are named after those instructions.

An op is charged to the layer of the innermost frame on its stack that
a layer's rules name (``metrics/<metric>.json`` ``charges``: a file
under ``src/repro`` and, optionally, a function).  A fusion that carries
no stack of its own takes the layer most of its fused instructions have.
An op that no rule names, or that runs outside the segment program, is
charged to the catch-all layer.  Device time is cut at every event
boundary and each piece is charged to the innermost event running then
(the one that started last), so every busy nanosecond is charged once:
the layers add up to the busy time, and busy time is the union of the
op intervals.
"""
from __future__ import annotations

import bisect
import collections
import glob
import heapq
import json
import os

OTHER = None      # the catch-all layer's key in a charge map

# ---------------------------------------------------------------------------
# the HLO module proto, as much of the protobuf wire format as is needed
# ---------------------------------------------------------------------------
# HloModuleProto: computations = 3, stack_frame_index = 17
# HloComputationProto: name = 1, instructions = 2, id = 5,
#   is_fusion_computation = 7
# HloInstructionProto: name = 1, opcode = 2, metadata = 7, id = 35,
#   called_computation_ids = 38;  OpMetadata: stack_frame_id = 15
# StackFrameIndexProto: file_names = 1, function_names = 2,
#   file_locations = 3 (file_name_id = 1, function_name_id = 2),
#   stack_frames = 4 (file_location_id = 1, parent_frame_id = 2); ids 1-based


def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield f, v


def _group(b) -> dict:
    d = collections.defaultdict(list)
    for f, v in _fields(b):
        d[f].append(v)
    return d


def _ints(values) -> list[int]:
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:                                   # packed
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def op_stacks(module_proto: bytes) -> dict[str, list[list[str]]]:
    """Instruction name -> stacks of ``"file:function"`` (innermost
    first) for every instruction of a computation that is not fused.
    One stack where the instruction has one; for a fusion without, one
    per fused instruction that has one."""
    top = _group(module_proto)
    sfi = _group(top[17][0]) if 17 in top else {}
    files = [x.decode() for x in sfi.get(1, [])]
    funcs = [x.decode() for x in sfi.get(2, [])]
    locs = [dict(_fields(x)) for x in sfi.get(3, [])]
    frames = [dict(_fields(x)) for x in sfi.get(4, [])]

    def stack(fid):
        out = []
        while fid:
            fr = frames[fid - 1]
            loc = locs[fr.get(1, 0) - 1]
            out.append(f"{files[loc.get(1, 0) - 1]}:{funcs[loc.get(2, 0) - 1]}")
            fid = fr.get(2, 0)
        return out

    comps = {}
    for cb in top[3]:
        c = _group(cb)
        instrs = []
        for ib in c.get(2, []):
            d = _group(ib)
            md = dict(_fields(d[7][0])) if 7 in d else {}
            instrs.append((d[1][0].decode(), d[2][0].decode(),
                           stack(md.get(15, 0)), _ints(d.get(38, []))))
        comps[c[5][0] if 5 in c else 0] = (bool(_ints(c.get(7, [0]))[0]),
                                           instrs)

    def fused_stacks(cid):
        out = []
        for _, _, st, called in comps[cid][1]:
            if st:
                out.append(st)
            for sub in called:
                if sub in comps and comps[sub][0]:
                    out += fused_stacks(sub)
        return out

    ops = {}
    for fused, instrs in comps.values():
        if fused:
            continue
        for name, opcode, st, called in instrs:
            if st:
                ops[name] = [st]
            elif opcode == "fusion":
                ops[name] = [s for c in called for s in fused_stacks(c)]
            else:
                ops[name] = []
    return ops


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def layer_rules(metric_defs) -> list[tuple[str, str, str | None]]:
    """(layer, file, function) charge rules from the metric definitions;
    a layer named by several metrics contributes its rules once."""
    rules, seen = [], set()
    for m in metric_defs:
        for c in m.get("charges", []):
            r = (m["layer"], c["file"], c.get("function"))
            if r not in seen:
                seen.add(r)
                rules.append(r)
    return rules


def _frame_layer(frame: str, rules):
    path, _, func = frame.rpartition(":")
    path = path.replace("\\", "/")
    short = func.rpartition(".")[2]
    for layer, file, function in rules:
        if f"/repro/{file}" in path and (path.endswith(file)
                                         or file.endswith("/")):
            if function is None or short == function:
                return layer
    return OTHER


def charge(stacks: list[list[str]], rules):
    """The layer of an op with these stacks (see the module docstring)."""
    votes = collections.Counter()
    for st in stacks:
        for fr in st:
            layer = _frame_layer(fr, rules)
            if layer is not OTHER:
                votes[layer] += 1
                break
    if not votes:
        return OTHER
    order = {r[0]: i for i, r in reversed(list(enumerate(rules)))}
    return max(votes, key=lambda k: (votes[k], -order[k]))


def charge_ops(stacks_by_op: dict, rules) -> dict:
    return {op: charge(st, rules) for op, st in stacks_by_op.items()}


# ---------------------------------------------------------------------------
# the device trace
# ---------------------------------------------------------------------------

def op_name(event_name: str) -> str:
    """A trace event's HLO instruction name: the TPU trace names an op
    event by its whole HLO text, ``%fusion.12 = f32[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(trace_dir: str, module: str) -> dict:
    """The device ops of a profiler trace, per chip, with the spans in
    which ``module`` ran, and the host's annotated spans."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    chips, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[op_name(e.name), e.start_ns, e.duration_ns]
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [[e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events
                            if e.name.split("(")[0] == module]
            chips[plane.name.rpartition(":")[2]] = {"ops": ops,
                                                    "modules": sorted(mods)}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name.startswith("bench.")]
    if not chips:
        raise ValueError(f"no TPU device planes in the trace under {trace_dir}")
    return {"chips": chips, "host": host}


def _inside(spans, t) -> bool:
    """Is ``t`` inside one of the sorted, disjoint ``spans``?"""
    k = bisect.bisect_right(spans, [t, float("inf")]) - 1
    return k >= 0 and spans[k][0] <= t < spans[k][1]


def _self_times(ops):
    """Per op event, the device time in which it is the innermost op
    running; and the busy intervals (their union)."""
    ev = sorted(((s, s + d, i) for i, (_, s, d) in enumerate(ops)),
                key=lambda x: (x[0], -x[1]))
    self_t = [0.0] * len(ops)
    bounds = sorted({b for s, e, _ in ev for b in (s, e)})
    heap, k, busy = [], 0, []
    for lo, hi in zip(bounds, bounds[1:]):
        while k < len(ev) and ev[k][0] <= lo:
            heapq.heappush(heap, (-ev[k][0], ev[k][1], ev[k][2]))
            k += 1
        while heap and heap[0][1] <= lo:
            heapq.heappop(heap)
        if not heap:
            continue
        # innermost = started last; drop events that ended underneath it
        self_t[heap[0][2]] += hi - lo
        if busy and busy[-1][1] == lo:
            busy[-1][1] = hi
        else:
            busy.append([lo, hi])
    return self_t, busy


def reduce(trace: dict, charges: dict, n_windows: int,
           layers: list[str]) -> dict:
    """Per-chip means of busy time and of each layer's device time (in
    seconds, over the traced window), the ops that took most time, and
    the longest idle gaps named by what the host was doing.  ``charges``
    maps op names of the segment program to layers (``OTHER`` for the
    rest)."""
    n = len(trace["chips"])
    layer_s = {k: 0.0 for k in layers}
    layer_s[OTHER] = 0.0
    busy_s, per_op, gaps = 0.0, collections.Counter(), []
    for chip in trace["chips"].values():
        ops = chip["ops"]
        self_t, busy = _self_times(ops)
        busy_s += sum(b - a for a, b in busy) * 1e-9 / n
        for (name, start, _), t in zip(ops, self_t):
            inside = (_inside(chip["modules"], start)
                      if chip["modules"] else True)
            layer = charges.get(name, OTHER) if inside else OTHER
            # a new metric may name a layer that an old trace never charged
            layer_s[layer if layer in layer_s else OTHER] += t * 1e-9 / n
            per_op[f"{layer or 'other'}/{name}"] += t * 1e-9 / n
        for (a0, a1), (b0, b1) in zip(busy, busy[1:]):
            gaps.append((b0 - a1, a1, b0))
    gaps.sort(reverse=True)
    idle = []
    for g, a, b in gaps[:10]:
        mid = (a + b) / 2
        what = [h for h, s, d in trace.get("host", []) if s <= mid < s + d]
        idle.append([what[-1] if what else "host: none annotated",
                     g * 1e-9])
    return {"busy_s": busy_s, "layer_s": layer_s, "n_windows": n_windows,
            "device_ops": [[k, v] for k, v in per_op.most_common(10)],
            "idle_gaps": idle}


def chip_peaks(kind: str, path: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]
