"""Roofline-term extraction from compiled dry-run artifacts (§Roofline).

Home of the repo's ONE analysis layer's hardware model: this module carries
the machine constants (``PEAK_FLOPS`` / ``HBM_BW`` / ``ICI_BW``) and the
``Roofline`` term extraction that both legs of ``repro.analyze`` build on —
``analyze/pattern.py``'s static per-backend cost model and the launch
tooling's compiled-artifact analysis.  ``repro.launch.analysis`` re-exports
everything here for compatibility (it was this file's original home).

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs   / (chips · PEAK_FLOPS)
    memory     = HLO_bytes   / (chips · HBM_BW)
    collective = coll_bytes  / (chips · ICI_BW)

``cost_analysis()`` provides HLO FLOPs and bytes accessed.  Collective bytes
are NOT in cost_analysis — we parse the post-optimization HLO text and sum the
output-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op (ring traffic ≈ output bytes per
participating device; the constant factors are absorbed into the comparison,
which is relative across iterations).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in an HLO result-type string."""
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


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes per collective kind from post-optimization HLO."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        # e.g.:  %all-reduce.3 = bf16[4096,5120]{1,0} all-reduce(...)
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[^ ]+)\s+([\w\-]+)", line)
        if not m:
            continue
        op = m.group(2)
        # strip "-start"/"-done" async suffixes; count only starts
        base = op.replace("-start", "").replace("-done", "")
        if base in _COLLECTIVES and not op.endswith("-done"):
            out[base] += _shape_bytes(m.group(1))
            counts[base] += 1
    out["_counts"] = counts  # type: ignore[assignment]
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_detail: Dict[str, int] = field(default_factory=dict)
    model_flops: float = 0.0
    memory_per_device: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — fraction of compiled compute that is
        'useful' model math (catches remat recompute and padding waste).
        Both totals are global (hlo_flops = per-device analyzer total × chips)."""
        if self.hlo_flops <= 0:
            return 0.0
        return self.model_flops / self.hlo_flops

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs utilization if the dominant term were the runtime:
        (model_flops / chips / PEAK) / max(term) — the score we hillclimb."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return (self.model_flops / (self.chips * PEAK_FLOPS)) / t

    def to_dict(self) -> Dict:
        d = asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            bottleneck=self.bottleneck,
            useful_ratio=self.useful_ratio,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def model_train_flops(n_params_active: int, n_tokens: int) -> float:
    """6·N·D (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * n_tokens


def model_forward_flops(n_params_active: int, n_tokens: int) -> float:
    return 2.0 * n_params_active * n_tokens


def model_attn_flops(cfg, seq_len: int, n_tokens: int, *, train: bool, decode: bool = False) -> float:
    """Quadratic attention term (not in 6·N·D; dominates at 32k+ context):
    4·T_ctx·(h·hd) per token per attention layer forward (QKᵀ + AV), ×3 for
    training (fwd+bwd).  Sliding windows cap the context; SSM layers have no
    quadratic term (their state math is inside the param count)."""
    kinds = cfg.layer_kinds
    n_attn = sum(1 for k in kinds if k in ("attn", "moe"))
    if cfg.shared_attn_every:
        n_attn += len(kinds) // cfg.shared_attn_every
    if n_attn == 0:
        return 0.0
    d_attn = cfg.n_heads * cfg.resolved_head_dim
    ctx = seq_len if cfg.sliding_window is None else min(seq_len, cfg.sliding_window)
    eff_ctx = ctx if decode else ctx / 2.0  # causal averaging over positions
    per_token = 4.0 * eff_ctx * d_attn * n_attn
    return per_token * n_tokens * (3.0 if train else 1.0)


def analyze_compiled(
    compiled, *, arch: str, shape: str, mesh_name: str, chips: int, model_flops: float
) -> Roofline:
    """Trip-count-aware analysis of the partitioned module (``hlo_stats``).

    The optimized HLO text is the per-device program; totals below are global
    (per-device × chips).  ``cost_analysis()`` is recorded for reference but
    under-counts ``while`` bodies (counted once), hence the custom analyzer.
    """
    from ..launch.hlo_stats import analyze_hlo_text

    stats = analyze_hlo_text(compiled.as_text())
    cost = compiled.cost_analysis()
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = float(
                getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                - getattr(ma, "alias_size_in_bytes", 0)
            )
    except Exception:
        pass
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=stats.flops * chips,
        hlo_bytes=stats.bytes * chips,
        coll_bytes=stats.coll_bytes * chips,
        coll_detail={
            **{k: v * chips for k, v in stats.coll.items()},
            "coll_ops_per_device": stats.coll_count,
            "unknown_trip_loops": stats.unknown_trips,
            "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
            "xla_cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
        },
        model_flops=model_flops,
        memory_per_device=mem,
    )
