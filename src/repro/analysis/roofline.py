"""Roofline terms from a compiled dry-run artifact.

Three terms per (arch x shape x mesh), from the peak rates of the device
kind the report is for (`PEAKS`, keyed by `jax.Device.device_kind`; the
dry-run meshes are TPU v5e, "TPU v5 lite"):

  compute    = HLO_FLOPs_per_device / peak_FLOPs          (v5e: 197 TF/s bf16)
  memory     = HLO_bytes_per_device / HBM_bw              (v5e: 819 GB/s)
  collective = collective_bytes_per_device / link_bw      (v5e: ~50 GB/s/link ICI)

``compiled.cost_analysis()`` yields per-device FLOPs and bytes (the module
is the post-SPMD per-device program). Collective bytes are NOT in
cost_analysis: we parse the optimized HLO text and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, splitting by whether the replica group set crosses the
"pod" axis (inter-pod links are the slower tier and are reported
separately)."""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peak rates."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per chip-to-chip link
    source: str


# keyed by jax.Device.device_kind; a kind that is not here is an error
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9,
        ici_bw=50e9,  # 1,600 Gbit/s of interconnect over 4 links
        source='Google Cloud documentation, "TPU v5e"',
    ),
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"  # the chip the production meshes describe
DCN_BW = 12.5e9  # bytes/s inter-pod (assumed 100 Gb/s NIC-class)


def device_peaks(kind: str) -> DevicePeaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r}; add its published "
                       f"peaks to repro.analysis.roofline.PEAKS") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)


def _parse_shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'f32[128,1024]' or a tuple."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]
    total_bytes: int
    inter_pod_bytes: int  # collectives whose replica groups cross pods

    def summary(self) -> str:
        parts = [f"{k}:{v}({self.bytes_by_kind[k]/1e6:.1f}MB)" for k, v in self.counts.items()]
        return " ".join(parts) if parts else "none"


def parse_collectives(
    hlo_text: str, n_devices: int = 0, pod_size: int = 0
) -> CollectiveStats:
    """Sum output-shape bytes of every collective op in optimized HLO.

    Output-shape bytes are the data crossing the interconnect per device
    (all-gather output = gathered bytes received; all-reduce output ~= 2x
    in a ring but we count payload once -- consistent, documented). Inter-pod
    split: a replica group that contains device ids from different pods
    (id // pod_size differs) crosses the pod boundary."""
    counts: Dict[str, int] = {}
    bts: Dict[str, int] = {}
    inter = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = _parse_shape_bytes(shape_str)
        counts[kind] = counts.get(kind, 0) + 1
        bts[kind] = bts.get(kind, 0) + b
        if pod_size and n_devices > pod_size:
            g = re.search(r"replica_groups=\{([^}]*)\}", line)
            gg = re.search(r"replica_groups=\[\d+,\d+\]<=\[(\d+)\]", line)
            crosses = False
            if g:
                first = g.group(1).split("},{")[0]
                ids = [int(x) for x in re.findall(r"\d+", first)]
                pods = {i // pod_size for i in ids}
                crosses = len(pods) > 1
            elif gg:
                # iota groups [n,m]<=[N]: groups stride over all devices
                crosses = True
            if crosses:
                inter += b
    return CollectiveStats(
        counts=counts,
        bytes_by_kind=bts,
        total_bytes=sum(bts.values()),
        inter_pod_bytes=inter,
    )


# opcodes that stay HBM traffic on a fusing backend (TPU): dots/convs read
# and write HBM; loop/collective/copy/scatter boundaries materialize; raw
# elementwise ops (convert/add/multiply/broadcast/...) fuse into neighbors
# and are NOT separately counted.
_MAJOR_OPS = {
    # ops whose operands/outputs genuinely stream through HBM on TPU; raw
    # elementwise chains, reduces, copies and loop plumbing fuse away.
    "dot", "convolution", "scatter", "gather",
    "dynamic-slice", "dynamic-update-slice", "sort", "rng",
}

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*((?:\([^)]*\)|\S+))\s+([\w\-]+)")


def fusion_adjusted_bytes(hlo_text: str, score_dims: Optional[Tuple[int, int]] = None):
    """Approximate post-fusion HBM traffic from optimized HLO text.

    Counts output bytes + operand bytes for _MAJOR_OPS only, resolving
    operand shapes through a name->bytes table (two passes). Elementwise ops
    are assumed fused (zero incremental traffic) -- this models the TPU
    backend; the raw cost_analysis number is the unfused upper bound.

    score_dims: optional (Sq, Skv) -- tensors whose trailing dims match are
    attention score matrices; their traffic is tallied separately because the
    Pallas flash kernel keeps them in VMEM on the TPU target.
    Returns (adjusted_bytes, score_bytes)."""
    name_bytes: Dict[str, int] = {}
    name_shape: Dict[str, str] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            name_bytes[m.group(1)] = _parse_shape_bytes(m.group(2))
            name_shape[m.group(1)] = m.group(2)

    def is_score(shape_str: str) -> bool:
        if score_dims is None:
            return False
        sq, skv = score_dims
        return f",{sq},{skv}]" in shape_str or f"[{sq},{skv}]" in shape_str

    total = 0
    scores = 0
    opnd_re = re.compile(r"(%?[\w.\-]+)")
    for line in lines:
        m = _DEF_RE.match(line)
        if not m or m.group(3) not in _MAJOR_OPS:
            continue
        out_b = _parse_shape_bytes(m.group(2))
        total += out_b
        if is_score(m.group(2)):
            scores += out_b
        if m.group(3) == "parameter":
            continue
        # operand names inside the call parens
        paren = line[line.find("(", m.end(3)) :]
        for om in opnd_re.finditer(paren):
            nm = om.group(1)
            if nm in name_bytes:
                total += name_bytes[nm]
                if is_score(name_shape.get(nm, "")):
                    scores += name_bytes[nm]
    return total, scores


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float  # raw cost_analysis (unfused upper bound)
    adj_bytes_per_device: float  # fusion-adjusted (major ops only)
    score_bytes_per_device: float  # attention-score traffic (flash keeps in VMEM)
    collective_bytes: float
    inter_pod_bytes: float
    model_flops: float  # analytic 6ND / 2ND
    peak_memory_bytes: float  # per-device (temp + args)
    peak_state_bytes: float  # per-device (args + outputs)
    collectives: Dict[str, int]
    device_kind: str = DRYRUN_DEVICE_KIND

    def __post_init__(self):
        self.peaks = device_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def t_memory_hlo(self) -> float:
        """Unfused upper bound (raw XLA-CPU bytes accessed)."""
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_memory(self) -> float:
        """TPU-target memory term: matmul/gather/scatter operand+output
        traffic, minus attention-score traffic (the Pallas flash kernel keeps
        scores in VMEM), plus one read+write of the program state (params,
        optimizer, inputs, outputs)."""
        state_rw = 2.0 * self.peak_state_bytes
        return (
            max(self.adj_bytes_per_device - self.score_bytes_per_device, 0.0)
            + state_rw
        ) / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        intra = (self.collective_bytes - self.inter_pod_bytes) / self.peaks.ici_bw
        inter = self.inter_pod_bytes / DCN_BW
        return intra + inter

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO flops summed over devices)."""
        total = self.flops_per_device * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: useful flops / (bound time x peak x chips)."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return self.model_flops / (t * self.peaks.flops * self.n_devices)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "device_kind": self.device_kind,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_memory_hlo_s": self.t_memory_hlo,
            "adj_bytes_per_dev": self.adj_bytes_per_device,
            "score_bytes_per_dev": self.score_bytes_per_device,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops_per_dev": self.flops_per_device,
            "useful_flops_frac": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
            "peak_mem_gb": self.peak_memory_bytes / 1e9,
            "collectives": self.collectives,
            "collective_bytes": self.collective_bytes,
            "inter_pod_bytes": self.inter_pod_bytes,
        }


def build_report(
    arch: str,
    shape: str,
    mesh_name: str,
    n_devices: int,
    cost: dict,
    mem,
    hlo_text: str,
    model_flops: float,
    pod_size: int = 256,
    score_dims: Optional[Tuple[int, int]] = None,
) -> RooflineReport:
    coll = parse_collectives(hlo_text, n_devices=n_devices, pod_size=pod_size)
    adj, scores = fusion_adjusted_bytes(hlo_text, score_dims=score_dims)
    flops = float(cost.get("flops", 0.0))
    by = float(cost.get("bytes accessed", 0.0))
    peak = float(mem.temp_size_in_bytes + mem.argument_size_in_bytes)
    state = float(mem.argument_size_in_bytes + mem.output_size_in_bytes)
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        n_devices=n_devices,
        flops_per_device=flops,
        bytes_per_device=by,
        adj_bytes_per_device=float(adj),
        score_bytes_per_device=float(scores),
        collective_bytes=float(coll.total_bytes),
        inter_pod_bytes=float(coll.inter_pod_bytes),
        model_flops=model_flops,
        peak_memory_bytes=peak,
        peak_state_bytes=state,
        collectives=coll.counts,
    )


def extrapolate_counts(v1: float, v2: float, groups: int) -> float:
    """Two-point depth extrapolation: counts are linear in layer-group count
    (module = base + G x per-group), so  M(G) = M(1) + (G-1) x (M(2)-M(1))."""
    return v1 + (groups - 1) * (v2 - v1)


def build_report_extrapolated(
    arch: str,
    shape: str,
    mesh_name: str,
    n_devices: int,
    cost1: dict,
    hlo1: str,
    cost2: dict,
    hlo2: str,
    groups: int,
    mem,
    model_flops: float,
    pod_size: int = 256,
    score_dims: Optional[Tuple[int, int]] = None,
) -> RooflineReport:
    """RooflineReport from 1-group and 2-group flops-mode lowerings."""
    c1 = parse_collectives(hlo1, n_devices=n_devices, pod_size=pod_size)
    c2 = parse_collectives(hlo2, n_devices=n_devices, pod_size=pod_size)
    a1, s1 = fusion_adjusted_bytes(hlo1, score_dims=score_dims)
    a2, s2 = fusion_adjusted_bytes(hlo2, score_dims=score_dims)
    ext = lambda x, y: extrapolate_counts(float(x), float(y), groups)
    counts = {
        k: int(round(ext(c1.counts.get(k, 0), c2.counts.get(k, 0))))
        for k in set(c1.counts) | set(c2.counts)
    }
    state = float(mem.argument_size_in_bytes + mem.output_size_in_bytes)
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        n_devices=n_devices,
        flops_per_device=ext(cost1.get("flops", 0.0), cost2.get("flops", 0.0)),
        bytes_per_device=ext(cost1.get("bytes accessed", 0.0),
                             cost2.get("bytes accessed", 0.0)),
        adj_bytes_per_device=ext(a1, a2),
        score_bytes_per_device=ext(s1, s2),
        collective_bytes=ext(c1.total_bytes, c2.total_bytes),
        inter_pod_bytes=ext(c1.inter_pod_bytes, c2.inter_pod_bytes),
        model_flops=model_flops,
        peak_memory_bytes=float(mem.temp_size_in_bytes + mem.argument_size_in_bytes),
        peak_state_bytes=state,
        collectives=counts,
    )
