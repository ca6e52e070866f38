"""Roofline analysis from a compiled dry-run artifact.

Three terms per (arch x shape x mesh), all in seconds-per-step:

    compute    = HLO_FLOPs_per_chip / PEAK_FLOPS_BF16
    memory     = HLO_bytes_per_chip / HBM_BW
    collective = max(ici_wire_bytes / ICI_BW,  dcn_wire_bytes / DCN_BW)

``compiled.cost_analysis()`` gives per-chip FLOPs/bytes (verified: an 8-way
sharded matmul reports 1/8 of global FLOPs).  Collective bytes are *not* in
cost_analysis — we parse the post-SPMD optimized HLO and sum wire traffic per
op with ring-collective cost models, classifying each op as intra-pod (ICI)
or cross-pod (DCN) by materialising its replica groups (512 ids) and checking
whether any group spans a pod boundary (id // 256).

MODEL_FLOPS uses the published 6*N*D (train) / 2*N*D (inference) approximation
with N = active params, D = tokens; the ratio MODEL_FLOPS / (HLO_FLOPs x chips)
exposes remat recompute, causal-masking waste and attention/routing overhead.
"""

from __future__ import annotations

import dataclasses
import json
import math
import re

import numpy as np

from .mesh import CHIPS_PER_POD, DCN_BW, HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# jax.named_scope markers for Pallas-kernel fusion regions (see
# hlo_analysis.analyze_hlo kernel_scopes)
KERNEL_SCOPES = ("fa_kernel_region", "ssd_kernel_region", "rglru_kernel_region")

_OP_RE = re.compile(
    r"=\s+(?P<ret>\(?[a-z0-9]+\[[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _shape_bytes(ret: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(ret):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_groups(line: str):
    """-> (group_size, groups ndarray | None)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        return s, ids.reshape(g, s)
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        groups = [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in re.findall(r"\{([^}]*)\}", m.group(1))
        ]
        if groups and groups[0]:
            return len(groups[0]), np.array(groups)
    return 1, None


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int
    cross_pod: bool
    wire_bytes: float  # per chip

    @staticmethod
    def wire(kind: str, nbytes: int, n: int) -> float:
        """Per-chip ring-collective wire bytes."""
        if n <= 1:
            return 0.0
        if kind == "all-reduce":
            return 2.0 * nbytes * (n - 1) / n
        if kind == "all-gather":
            return nbytes * (n - 1) / n          # nbytes = gathered (full) size
        if kind == "reduce-scatter":
            return nbytes * (n - 1)              # nbytes = shard (result) size
        if kind == "all-to-all":
            return nbytes * (n - 1) / n
        if kind == "collective-permute":
            return float(nbytes)
        return 0.0


def parse_collectives(hlo_text: str, chips_per_pod: int = CHIPS_PER_POD) -> list[CollectiveOp]:
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None or "-done" in line[: m.start() + 20]:
            continue
        kind = m.group("op")
        nbytes = _shape_bytes(m.group("ret"))
        gsize, groups = _parse_groups(line)
        cross = False
        if groups is not None:
            cross = bool((groups // chips_per_pod != groups[:, :1] // chips_per_pod).any())
        out.append(
            CollectiveOp(
                kind=kind,
                result_bytes=nbytes,
                group_size=gsize,
                cross_pod=cross,
                wire_bytes=CollectiveOp.wire(kind, nbytes, gsize),
            )
        )
    return out


def model_flops(cfg, shape) -> float:
    """Published approximation: 6*N*D train, 2*N*D inference."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one new token per row
    return 2.0 * n_active * tokens


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    ici_bytes: float
    dcn_bytes: float
    n_collectives: int
    model_flops: float
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0

    @property
    def compute_s(self) -> float:
        return self.flops_per_chip / PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_chip / HBM_BW

    @property
    def collective_s(self) -> float:
        return max(self.ici_bytes / ICI_BW, self.dcn_bytes / DCN_BW)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s, "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        hlo_global = self.flops_per_chip * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilisation at the roofline step time."""
        denom = self.step_s * self.chips * PEAK_FLOPS_BF16
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(
            compute_s=self.compute_s, memory_s=self.memory_s,
            collective_s=self.collective_s, dominant=self.dominant,
            step_s=self.step_s, useful_ratio=self.useful_ratio, mfu=self.mfu,
        )
        return d


def analyze(compiled, *, arch: str, shape, cfg, mesh_name: str, chips: int):
    """-> (Roofline, HLOCost).  FLOPs/bytes are *loop-corrected*:

    cost_analysis() counts while bodies once, so we re-derive FLOPs from the
    HLO dot/conv inventory with trip-count multiplicity (hlo_analysis), and
    scale cost_analysis' byte count by the (multiplicity-aware / body-once)
    ratio of our instruction-level byte model — calibrating our model's
    absolute conventions against XLA's while keeping the loop correction."""
    from .hlo_analysis import analyze_hlo

    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    hc = analyze_hlo(text, kernel_scopes=KERNEL_SCOPES)
    # Byte calibration: our instruction-level model overcounts ~3-4x vs XLA's
    # HloCostAnalysis conventions (fusion-interior traffic).  Anchor the
    # absolute scale to cost_analysis() (body-once, unscoped) and apply our
    # model's *ratio* for the two corrections it adds: while-loop trip counts
    # and Pallas-kernel VMEM regions.
    hc_once = analyze_hlo(text, unroll_while=False)
    ratio = hc.bytes / hc_once.bytes if hc_once.bytes else 1.0
    bytes_corrected = float(ca.get("bytes accessed", 0.0)) * ratio
    r = Roofline(
        arch=arch,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        flops_per_chip=hc.flops,
        bytes_per_chip=bytes_corrected,
        ici_bytes=hc.ici_wire,
        dcn_bytes=hc.dcn_wire,
        n_collectives=int(sum(v["count"] for v in hc.collectives.values())),
        model_flops=model_flops(cfg, shape),
        argument_bytes=getattr(ma, "argument_size_in_bytes", 0),
        output_bytes=getattr(ma, "output_size_in_bytes", 0),
        temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
    )
    return r, hc


def format_row(r: Roofline) -> str:
    return (
        f"{r.arch:<18} {r.shape:<12} {r.mesh:<9} "
        f"c={r.compute_s:9.4f}s m={r.memory_s:9.4f}s x={r.collective_s:9.4f}s "
        f"dom={r.dominant:<10} useful={r.useful_ratio:6.2f} mfu={r.mfu:6.3f}"
    )
