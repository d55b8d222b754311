"""From a profiler trace of a few steps to the per-layer numbers.

A device op is joined to its instruction in the step's HLO text
(``compiled.as_text()``) by its name. The instruction's op_name holds the
named scope the harness put around the call into its layer, where there
is one; otherwise its metadata's stack of source frames decides, by the
innermost frame under ``src/repro/`` (``layers.json``). Many ops of the
step lose their frames in lowering, so the scopes carry most of the
attribution. Each op of the step also has the program's stage that its
op_name names (``repro.core.stages.stage_of``); an op that names none
but whose ``calls=`` computation holds staged instructions, such as a
fusion, is counted apart under the most common of their stages. Each op
counts its self time: its duration less that of the ops nested inside
it on the same line. Busy time is the union
of op intervals inside the traced window, which the host span named
``window`` bounds; idle is the rest. A collective's exposed time is the
part of it during which no other op runs on that device.
"""
import bisect
import collections
import glob
import json
import os
import re

from repro.core.stages import stage_of

HERE = os.path.dirname(os.path.abspath(__file__))
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
HOST_SPANS = ("dispatch", "loss_fetch", "block")

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_META_FRAME = re.compile(r"stack_frame_id=(\d+)")
_META_NAME = re.compile(r'op_name="([^"]*)"')
_META_FILE = re.compile(r'source_file="([^"]+)"(?:\s+source_line=(\d+))?')
_FILE_NAME = re.compile(r'^(\d+)\s+"(.*)"$')
_FILE_LOC = re.compile(r"^(\d+)\s+\{file_name_id=(\d+)\s+function_name_id=\d+"
                       r"\s+line=(\d+)")
_FRAME = re.compile(r"^(\d+)\s+\{file_location_id=(\d+)\s+parent_frame_id="
                    r"(\d+)")


def load_layers(path=os.path.join(HERE, "layers.json")):
    with open(path) as f:
        return json.load(f)


def _repro_path(path):
    i = path.rfind("/repro/")
    return path[i + 1:] if i >= 0 else None


def hlo_sources(hlo_text, layers=None):
    """{instruction name: (opcode, layer, innermost repro file or None,
    line, stage, inner stage)}. The stage is the one its own op_name
    names, or None; the inner stage, for an instruction with none, is the
    most common stage of the instructions of the computation it
    ``calls=``, or None."""
    layers = layers or load_layers()
    scope = re.compile(re.escape(layers["scope_prefix"]) + r"([A-Za-z]+)")
    files, locs, frames = {}, {}, {}
    section = comp = None
    ops, comp_stages = {}, collections.defaultdict(collections.Counter)
    for line in hlo_text.splitlines():
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames"):
            section = s
            continue
        if section and not s:
            section = None
            continue
        if section == "FileNames":
            m = _FILE_NAME.match(s)
            if m:
                files[int(m.group(1))] = m.group(2)
        elif section == "FileLocations":
            m = _FILE_LOC.match(s)
            if m:
                locs[int(m.group(1))] = (int(m.group(2)), int(m.group(3)))
        elif section == "StackFrames":
            m = _FRAME.match(s)
            if m:
                # parent_frame_id is the parent's id plus one; 0 is none
                frames[int(m.group(1))] = (int(m.group(2)),
                                           int(m.group(3)) - 1)
        elif section is None:
            m = _INSTR.match(line)
            if m:
                mn = _META_NAME.search(line)
                stage = stage_of(mn.group(1)) if mn else None
                ops[m.group(1)] = (m.group(2), line, stage)
                if stage and comp:
                    comp_stages[comp][stage] += 1
            elif not line[:1].isspace():
                mc = _COMP.match(line)
                comp = mc.group(1) if mc else None
    out = {}
    for name, (opcode, line, stage) in ops.items():
        src = (None, 0)
        fm = _META_FRAME.search(line)
        if fm:
            fid, seen = int(fm.group(1)), set()
            while fid in frames and fid not in seen:
                seen.add(fid)
                loc_id, parent = frames[fid]
                file_id, lineno = locs.get(loc_id, (None, 0))
                path = _repro_path(files.get(file_id, ""))
                if path:
                    src = (path, lineno)
                    break
                fid = parent
        else:
            mf = _META_FILE.search(line)
            if mf and _repro_path(mf.group(1)):
                src = (_repro_path(mf.group(1)), int(mf.group(2) or 0))
        mn = _META_NAME.search(line)
        scoped = scope.findall(mn.group(1)) if mn else []
        layer = scoped[-1] if scoped else layer_of(src[0], layers)
        inner = None
        if stage is None:
            held = sum((comp_stages[c] for c in _CALLS.findall(line)),
                       collections.Counter())
            inner = held.most_common(1)[0][0] if held else None
        out[name] = (opcode, layer, src[0], src[1], stage, inner)
    return out


def layer_of(path, layers):
    if path is None:
        return "unattributed"
    for prefix, layer in layers["prefixes"]:
        if path.startswith(prefix):
            return layer
    return "unattributed"


def is_collective(opcode):
    return any(opcode.startswith(c) for c in COLLECTIVES)


def _self_times(events):
    """events [(start, end, ...)] on one line -> self ns of each, nested
    events taken out of their parent."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_ns = [e[1] - e[0] for e in events]
    stack = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


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


def op_name(event_name):
    """"%fusion.7 = bf16[..] fusion(..)" -> "fusion.7"."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(name):
    """"jit_step_fn(6858884246031076970)" or "HloModule jit_step_fn, .."
    -> "jit_step_fn"."""
    name = name.split("HloModule", 1)[-1].strip()
    return re.split(r"[\s,(]", name, 1)[0]


def _modules_of(ops, modules):
    """The module each op ran in: the module event whose span holds the
    op's start."""
    spans = sorted((s, s + d, module_name(n)) for s, d, n in modules)
    starts = [s for s, _, _ in spans]
    out = []
    for s, _, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append(spans[i][2] if i >= 0 and s < spans[i][1] else "")
    return out


def reduce_events(trace, sources, *, steps, j_local, peaks, step_module,
                  layers=None):
    """trace: {"devices": {device: {"ops": [[start_ns, dur_ns, name]],
    "modules": [[start_ns, dur_ns, name]]}}, "host": [[name, start_ns,
    dur_ns]]}, as read_xplane gives it; sources: hlo_sources() of the
    step, whose module is ``step_module``. Returns the reduction that the
    metric readers take: per device, self ns by layer (``layer_ns``) and,
    over the step module's ops alone, by stage (``stage_ns``, with the ops
    that name no stage, in themselves or inside, under ``unstaged``) and,
    for ops whose stage is only inside the computation they call, by that
    stage (``stage_inner_ns``, in no stage's sum)."""
    layers = layers or load_layers()
    host = trace["host"]
    win = [h for h in host if h[0] == "window"]
    spans = [(h[1], h[1] + h[2], h[0]) for h in host if h[0] in HOST_SPANS]
    per_dev, gaps0, ops0 = {}, [], collections.Counter()
    first = min(trace["devices"])
    for dev in sorted(trace["devices"]):
        evs = trace["devices"][dev]["ops"]
        mods = _modules_of(evs, trace["devices"][dev]["modules"])
        if win:
            w0, w1 = win[0][1], win[0][1] + win[0][2]
        else:
            w0 = min(e[0] for e in evs)
            w1 = max(e[0] + e[1] for e in evs)
        iv = [(max(s, w0), min(s + d, w1), op_name(n), m)
              for (s, d, n), m in zip(evs, mods) if s < w1 and s + d > w0]
        self_ns = _self_times(iv)
        layer_ns = collections.Counter()
        stage_ns, inner_ns = collections.Counter(), collections.Counter()
        coll, other = [], []
        for (s, e, op, module), ns in zip(iv, self_ns):
            if module == step_module:
                opcode, layer, path, lineno, stage, inner = sources.get(
                    op, ("", "unattributed", None, 0, None, None))
                if stage or not inner:
                    stage_ns[stage or "unstaged"] += ns
                else:
                    inner_ns[inner] += ns
            else:
                opcode, layer, path, lineno = "", "other:" + module, None, 0
            if is_collective(opcode):
                layer += "_collective"
                if layer == "sync_collective":
                    coll.append((s, e))
            else:
                other.append((s, e))
            layer_ns[layer] += ns
            if dev == first:
                where = f"{path}:{lineno}" if path else module
                ops0[f"{op} [{layer}] {where}"] += ns
        busy = _union([(s, e) for s, e, *_ in iv])
        busy_ns = sum(e - s for s, e in busy)
        cu = _union(coll)
        exposed = sum(e - s for s, e in cu) - _overlap(cu, _union(other))
        per_dev[dev] = {"layer_ns": dict(layer_ns),
                        "stage_ns": dict(stage_ns),
                        "stage_inner_ns": dict(inner_ns), "busy_ns": busy_ns,
                        "window_ns": w1 - w0, "sync_exposed_ns": exposed}
        if dev == first:
            edges = [w0] + [x for b in busy for x in b] + [w1]
            gaps0 = [(s, e) for s, e in zip(edges[::2], edges[1::2])
                     if e > s]
    named = []
    for s, e in sorted(gaps0, key=lambda g: g[0] - g[1])[:10]:
        best, what = 0, "host:none"
        for hs, he, name in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, what = ov, "host:" + name
        named.append([what, (e - s) / 1e9])
    n_dev = len(per_dev)
    return {
        "steps": steps,
        "j_local": j_local,
        "peaks": peaks,
        "devices": per_dev,
        "busy_s": sum(d["busy_ns"] for d in per_dev.values()) / n_dev / 1e9,
        "window_s": max(d["window_ns"] for d in per_dev.values()) / 1e9,
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in ops0.most_common(10)],
            "idle_gaps": named,
        },
    }


def read_xplane(path):
    """The trace in reduce_events' form from an .xplane.pb: the "XLA Ops"
    and "XLA Modules" lines of each TPU plane, and the events of the host
    threads."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(int(plane.name.rsplit(":", 1)[1]),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [[ev.start_ns, ev.duration_ns, ev.name]
                                 for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, ev.start_ns, ev.duration_ns]
                         for ev in line.events]
    return {"devices": devices, "host": host}


def reduce_dir(trace_dir, hlo_text, *, steps, j_local, peaks):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    trace = read_xplane(paths[0])
    if not any(d["ops"] for d in trace["devices"].values()):
        raise ValueError("the trace holds no TPU device ops")
    return reduce_events(trace, hlo_sources(hlo_text), steps=steps,
                         j_local=j_local, peaks=peaks,
                         step_module=module_name(hlo_text))
