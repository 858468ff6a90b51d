"""From a profiler trace to device times: the reduction every PR shares.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into three
event lists (start and end in ns on the profiler's common clock):

* ``modules``: the device's "XLA Modules" line, one event per program
  run (``jit__pf(...)`` is the engine's prefill, ``jit__dp(...)`` its
  decode step, ``jit_adopt_slot(...)`` / ``jit_release_slot(...)`` the
  slot moves);
* ``ops``: the device's "XLA Ops" line, one event per HLO op; a Pallas
  kernel appears as its custom call, named after the jitted kernel
  function (``mgs_matmul_exact_fused_pallas``,
  ``vmap_jit_mgs_matmul_exact_fused_pallas__`` under ``vmap``,
  ``mgs_paged_flash_attention``);
* ``host``: the host's Python and runtime threads, to say what the host
  was doing while the device sat idle.

``reduce`` attributes each kernel op to its kernel by name and to the
program whose module event encloses it, and fails loudly when a name it
needs is missing.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Tuple

#: program classes, by the prefix of the module event's name
PROGRAMS = (("prefill", "jit__pf("), ("decode", "jit__dp("),
            ("adopt", "jit_adopt_slot("), ("release", "jit_release_slot("))
#: kernels, by a pattern on the op's short name
KERNELS = (("matmul", re.compile(r"(^|_)mgs_matmul_exact_fused_pallas")),
           ("paged_attn", re.compile(r"^mgs_paged_flash_attention")))
HOST_LINES = ("python3", "main")
#: ops that only enclose others (a scan's loop): not counted as leaf ops
ENCLOSING = ("while", "conditional", "call")

Event = Tuple[str, float, float]


def short_name(op: str) -> str:
    """``'%mgs_matmul_exact_fused_pallas.92 = f32[...] ...'`` ->
    ``'mgs_matmul_exact_fused_pallas'``."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def program_of(module: str) -> str:
    for cls, prefix in PROGRAMS:
        if module.startswith(prefix):
            return cls
    return "other"


def load(path: str) -> Dict[str, List[Event]]:
    """Event lists of the first TPU device and the host, from an xplane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, List[Event]] = {"modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    out[key] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.split("/")[0] in HOST_LINES:
                    out["host"].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
    if not out["modules"] or not out["ops"]:
        raise ValueError(f"{path}: no TPU:0 'XLA Modules'/'XLA Ops' events")
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def save_events(events: Dict[str, List[Event]], path: str):
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> Dict[str, List[Event]]:
    with gzip.open(path, "rt") as f:
        return {k: [tuple(e) for e in v] for k, v in json.load(f).items()}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Reduced:
    busy_s: float
    program_s: Dict[str, float]
    program_n: Dict[str, int]
    kernel_s: Dict[Tuple[str, str], float]
    device_ops: List[Tuple[str, float]]      # top leaf ops by time
    idle_gaps: List[Tuple[str, float]]       # longest gaps, host label


def reduce(ev: Dict[str, List[Event]], top: int = 10) -> Reduced:
    modules = sorted(ev["modules"], key=lambda e: e[1])
    program_s: Dict[str, float] = {}
    program_n: Dict[str, int] = {}
    for name, s, e in modules:
        cls = program_of(name)
        program_s[cls] = program_s.get(cls, 0.0) + (e - s) * 1e-9
        program_n[cls] = program_n.get(cls, 0) + 1
    starts = [s for _, s, _ in modules]

    def enclosing(t: float) -> str:
        import bisect
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and modules[i][2] >= t:
            return program_of(modules[i][0])
        return "other"

    kernel_s: Dict[Tuple[str, str], float] = {}
    op_s: Dict[str, float] = {}
    leaf = []
    for name, s, e in ev["ops"]:
        sn = short_name(name)
        if sn.startswith(ENCLOSING):
            continue
        leaf.append((s, e))
        op_s[sn] = op_s.get(sn, 0.0) + (e - s) * 1e-9
        for kernel, pat in KERNELS:
            if pat.search(sn):
                key = (kernel, enclosing(s))
                kernel_s[key] = kernel_s.get(key, 0.0) + (e - s) * 1e-9
                break
    busy = _union(leaf + [(s, e) for _, s, e in modules])
    busy_s = sum(e - s for s, e in busy) * 1e-9
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:top]
    host = sorted(ev["host"], key=lambda h: h[2] - h[1])

    def label(lo: float, hi: float) -> str:
        mid = 0.5 * (lo + hi)
        for name, s, e in host:               # innermost: shortest first
            if s <= mid <= e:
                return name
        return "(no host event)"

    return Reduced(
        busy_s=busy_s, program_s=program_s, program_n=program_n,
        kernel_s=kernel_s,
        device_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(label(lo, hi), g * 1e-9) for g, lo, hi in gaps])


def check(red: Reduced, *, prefills: int, decode_rounds: int):
    """Fail loudly where the host saw work the trace does not name."""
    missing = []
    if prefills and not red.program_n.get("prefill"):
        missing.append("prefill program 'jit__pf(...)'")
    if decode_rounds and not red.program_n.get("decode"):
        missing.append("decode program 'jit__dp(...)'")
    if decode_rounds and not red.kernel_s.get(("matmul", "decode")):
        missing.append("fused matmul in decode ('mgs_matmul_exact_fused_pallas')")
    if decode_rounds and not red.kernel_s.get(("paged_attn", "decode")):
        missing.append("paged attention in decode ('mgs_paged_flash_attention')")
    if prefills and not red.kernel_s.get(("matmul", "prefill")):
        missing.append("fused matmul in prefill ('mgs_matmul_exact_fused_pallas')")
    if missing:
        raise ValueError("trace lacks names the reduction needs: "
                         + "; ".join(missing))
