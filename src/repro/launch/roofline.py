"""Roofline terms from a compiled dry-run artifact (no hardware needed).

Three terms per (arch × shape × mesh), all in SECONDS per step:

    compute    = FLOPs_per_device / PEAK_FLOPS_BF16
    memory     = HBM_bytes_per_device / HBM_BW
    collective = wire_bytes_per_device / ICI_BW

Sources — and the two XLA:CPU gotchas they work around:

  * ``cost_analysis()`` counts while-loop bodies ONCE.  A 61-layer scanned
    transformer would be undercounted 61×.  FLOPs therefore come from the
    jaxpr (``flopcount`` — exact dot_general accounting with scan lengths),
    divided by chips.
  * Memory + collective traffic comes from the OPTIMIZED HLO text, sectioned
    per computation; each while body's traffic is multiplied by its trip
    count (recovered from the loop-condition constant).  Per-op traffic =
    Σ operand/result buffer bytes (post-fusion: those are real HBM buffers).
    Collectives get ring-algorithm wire factors over their replica-group
    size g: all-reduce 2(g-1)/g, all-gather/reduce-scatter/all-to-all
    (g-1)/g, collective-permute 1.

The dominant term is the bottleneck; §Perf hillclimbs whatever dominates.
"""

from __future__ import annotations

import dataclasses
import re

from repro.launch.mesh import V5E, peaks

# the dry-run models v5e pods: their published peaks are the denominators
PEAK_FLOPS_BF16, HBM_BW, ICI_BW = peaks(V5E)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)"
)
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONST_RE = re.compile(r"constant\((\d+)\)")

_SKIP_OPS = (
    "parameter(", "constant(", "tuple(", "get-tuple-element(", "bitcast(",
    "after-all(", "partition-id(", "replica-id(",
)


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CompStats:
    mem_bytes: float = 0.0
    coll: dict = dataclasses.field(default_factory=lambda: {k: 0.0 for k in _COLL_KINDS})
    whiles: list = dataclasses.field(default_factory=list)  # (cond, body)
    calls: list = dataclasses.field(default_factory=list)  # fusion/call refs
    max_const: int = 1


def parse_hlo(text: str) -> dict[str, CompStats]:
    comps: dict[str, CompStats] = {}
    cur: CompStats | None = None
    entry = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if raw and not raw[0].isspace():
            m = _COMP_HDR_RE.match(raw.strip())
            if m and "{" in raw:
                name = m.group(1)
                cur = comps.setdefault(name, CompStats())
                if raw.startswith("ENTRY"):
                    entry = name
                continue
        if cur is None or "=" not in line:
            continue
        mc = _CONST_RE.search(line)
        if mc:
            cur.max_const = max(cur.max_const, int(mc.group(1)))
        if any(s in line for s in _SKIP_OPS):
            continue
        mw = _WHILE_RE.search(line)
        if mw:
            cur.whiles.append((mw.group(1), mw.group(2)))
            continue
        # called computations (fusions etc.) — traffic counted at call site
        for kind in _COLL_KINDS:
            if f" {kind}(" in line or f" {kind}-start(" in line:
                # result type(s) sit between '=' and the op keyword; tuple
                # outputs (multi-operand all-reduce) contain parens, so cut
                # at the op token rather than the first '('.
                rest = line.split("=", 1)[1]
                idx = rest.find(f" {kind}")
                nbytes = _shape_bytes(rest[:idx] if idx > 0 else rest)
                g = 1
                gm = _GROUPS_IOTA_RE.search(line)
                if gm:
                    g = int(gm.group(2))
                else:
                    gm = _GROUPS_RE.search(line)
                    if gm and gm.group(1).strip():
                        g = len(gm.group(1).split(","))
                if g > 1:
                    f = (
                        2.0 * (g - 1) / g if kind == "all-reduce"
                        else 1.0 if kind == "collective-permute"
                        else (g - 1) / g
                    )
                    cur.coll[kind] += nbytes * f
                break
        # memory traffic: all buffer shapes on the op line (result + operands)
        cur.mem_bytes += _shape_bytes(line)
    comps["__entry__"] = comps.get(entry, CompStats()) if entry else CompStats()
    if entry:
        comps["__entry_name__"] = entry  # type: ignore[assignment]
    return comps


def _aggregate(comps: dict, name: str, mult: float, out: dict, seen: tuple) -> None:
    if name not in comps or name in seen:
        return
    st = comps[name]
    out["mem"] += st.mem_bytes * mult
    for k in _COLL_KINDS:
        out[k] += st.coll[k] * mult
    for cond, body in st.whiles:
        trips = max(comps.get(cond, CompStats()).max_const, 1)
        _aggregate(comps, cond, mult * trips, out, seen + (name,))
        _aggregate(comps, body, mult * trips, out, seen + (name,))


def hlo_traffic(text: str) -> dict:
    """Loop-aware per-device traffic: {'mem': bytes, <coll-kind>: wire bytes}."""
    comps = parse_hlo(text)
    entry = comps.get("__entry_name__")
    out = {"mem": 0.0, **{k: 0.0 for k in _COLL_KINDS}}
    if isinstance(entry, str):
        _aggregate(comps, entry, 1.0, out, ())
    else:  # fallback: flat sum, no loop multipliers
        for name, st in comps.items():
            if isinstance(st, CompStats):
                out["mem"] += st.mem_bytes
                for k in _COLL_KINDS:
                    out[k] += st.coll[k]
    return out


@dataclasses.dataclass
class Roofline:
    name: str
    mesh: str
    chips: int
    flops_per_dev: float  # jaxpr-exact, / chips
    bytes_per_dev: float  # HLO loop-aware traffic
    wire_bytes_per_dev: float
    model_flops: float
    coll_detail: dict
    peak_mem_bytes: float = 0.0
    cost_analysis_flops: float = 0.0  # raw XLA numbers, for reference
    cost_analysis_bytes: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / (global program FLOPs): remat/redundancy waste."""
        total = self.flops_per_dev * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_frac(self) -> float:
        """Useful model FLOP/s at the bound step time vs chip peak."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return (self.model_flops / self.chips / t) / PEAK_FLOPS_BF16

    def to_dict(self) -> dict:
        return {
            "name": self.name, "mesh": self.mesh, "chips": self.chips,
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "wire_bytes_per_dev": self.wire_bytes_per_dev,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_frac": self.roofline_frac,
            "peak_mem_bytes": self.peak_mem_bytes,
            "coll_detail": self.coll_detail,
            "cost_analysis_flops": self.cost_analysis_flops,
            "cost_analysis_bytes": self.cost_analysis_bytes,
        }


def analyze(name, mesh_name, chips, compiled, model_flops, jaxpr_flops) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    txt = compiled.as_text()
    traffic = hlo_traffic(txt)
    wire = sum(v for k, v in traffic.items() if k != "mem")
    peak = 0.0
    try:
        ma = compiled.memory_analysis()
        peak = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
        )
    except Exception:
        pass
    return Roofline(
        name=name, mesh=mesh_name, chips=chips,
        flops_per_dev=jaxpr_flops / chips,
        bytes_per_dev=traffic["mem"],
        wire_bytes_per_dev=wire, model_flops=model_flops,
        coll_detail=traffic, peak_mem_bytes=peak,
        cost_analysis_flops=float(cost.get("flops", 0.0)),
        cost_analysis_bytes=float(cost.get("bytes accessed", 0.0)),
    )


def fmt_row(r: Roofline) -> str:
    return (
        f"{r.name:42s} {r.mesh:9s} "
        f"c={r.t_compute*1e3:9.3f}ms m={r.t_memory*1e3:9.3f}ms "
        f"x={r.t_collective*1e3:9.3f}ms -> {r.bottleneck:10s} "
        f"useful={r.useful_flops_frac*100:5.1f}% roof={r.roofline_frac*100:5.1f}% "
        f"hbm={r.peak_mem_bytes/2**30:6.2f}G"
    )
