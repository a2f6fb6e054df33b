"""Three-term roofline from dry-run artifacts.

    compute    = HLO_FLOPs   / (chips × peak bf16 FLOP/s)
    memory     = HLO_bytes   / (chips × peak HBM B/s)
    collective = coll_bytes  / (chips × ICI B/s per link)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` of the
PER-DEVICE partitioned module — i.e. already divided by the device count —
so the per-chip terms divide by 1; we keep the formulas in per-chip form and
document it. collective_bytes is parsed from the post-SPMD HLO (per device).
The peaks come from :data:`PEAKS`, keyed by the artifact's
``device_kind``.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s per chip
    int8_ops: float          # OP/s per chip
    hbm_bw: float            # B/s per chip
    ici_bw: float            # B/s per link


# Published per-chip peaks keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is TPU v5e. Source: Google Cloud documentation, "TPU v5e"
# (https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
# int8, 819 GB/s HBM, 1,600 Gbit/s of ICI over 4 links (50 GB/s each).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes_accessed: float
    collective_bytes: float
    model_flops: float
    peak_flops: float
    dram_s: float = 0.0          # analytic DRAM-stream estimate (see
    #                              analytic.cell_bytes — fusion-aware floor)

    @property
    def dominant(self) -> str:
        """Dominant term, judged against the *fused* DRAM floor (dram_s):
        ``memory_s`` (raw HLO bytes) assumes zero fusion and would classify
        every cell memory-bound; XLA:TPU fuses elementwise chains, so the
        floor is the realistic stream count. Both are reported."""
        mem = self.dram_s if self.dram_s > 0 else self.memory_s
        terms = {"compute": self.compute_s, "memory": mem,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        mem = self.dram_s if self.dram_s > 0 else self.memory_s
        return max(self.compute_s, mem, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is 'useful'
        (catches remat/redundancy waste). >1 ⇒ compiler fused away work;
        <1 ⇒ remat / overhead."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization *upper bound* implied by the roofline:
        useful FLOPs / (chip peak × bound time)."""
        if self.bound_s == 0:
            return 0.0
        return self.model_flops / (self.peak_flops * self.bound_s)

    def row(self) -> Dict[str, float]:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
        }


def _chips(art: Dict) -> int:
    return 512 if art.get("mesh") == "2x16x16" else 256


def from_artifact(art: Dict, corrected: bool = True) -> Optional[Roofline]:
    """Per-chip roofline from a dry-run JSON artifact.

    ``corrected=True`` replaces the raw cost_analysis FLOPs with the analytic
    per-cell model (divided by chips) when the artifact was NOT compiled with
    unrolled scans — XLA's CPU cost model counts while-loop bodies once
    (§Roofline-methodology). Bytes are scaled by the same factor (weight and
    activation traffic are also per-layer). Unrolled artifacts are exact and
    used verbatim. The artifact's ``device_kind`` picks the peaks.
    """
    peaks = peaks_for(art.get("device_kind"))
    cost = art.get("cost_analysis") or {}
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = float((art.get("collectives") or {})
                 .get("total", {}).get("bytes", 0.0))
    chips = _chips(art)
    model_fl = float(art.get("model_flops", 0.0)) / chips
    dram = 0.0

    if corrected and not art.get("unroll") and art.get("arch"):
        try:
            from repro.analysis import analytic
            from repro.config import cells_for_arch
            from repro.models import model_zoo
            cfg = model_zoo.get_config(art["arch"])
            cell = next(c for c in cells_for_arch(art["arch"])
                        if c.name == art["cell"])
            # FLOPs: analytic (validated vs unrolled HLO; CPU cost model
            # counts loop bodies once). Bytes: keep raw HLO (the prescribed
            # metric) but scale by the loop-repeat factor so per-layer
            # streams are counted L× — for decode (ratio≈1) this is a no-op.
            # Collectives: raw (dominant grad all-reduces sit outside loops).
            ana = analytic.cell_flops(cfg, cell) / chips
            if flops > 0 and ana > flops:
                byts *= ana / flops
            flops = max(ana, flops)
            dram = analytic.cell_bytes(cfg, cell) / chips
        except Exception:       # noqa: BLE001 — fall back to raw numbers
            pass

    return Roofline(
        compute_s=flops / peaks.bf16_flops,
        memory_s=byts / peaks.hbm_bw,
        collective_s=coll / peaks.ici_bw,
        flops=flops,
        bytes_accessed=byts,
        collective_bytes=coll,
        model_flops=model_fl,
        peak_flops=peaks.bf16_flops,
        dram_s=dram / peaks.hbm_bw,
    )


def load_artifacts(directory: str) -> Dict[str, Dict]:
    out = {}
    if not os.path.isdir(directory):
        return out
    for name in sorted(os.listdir(directory)):
        if name.endswith(".json"):
            with open(os.path.join(directory, name)) as f:
                out[name[:-5]] = json.load(f)
    return out


def table(directory: str) -> str:
    """Markdown roofline table for EXPERIMENTS.md."""
    arts = load_artifacts(directory)
    lines = [
        "| arch × cell | compute (s) | memory (s) | collective (s) | "
        "dominant | useful FLOPs ratio | MFU bound |",
        "|---|---|---|---|---|---|---|",
    ]
    for key, art in arts.items():
        if not art.get("ok"):
            lines.append(f"| {key} | FAILED: {art.get('error','?')[:60]} "
                         "| | | | | |")
            continue
        r = from_artifact(art)
        lines.append(
            f"| {key} | {r.compute_s:.4f} | {r.memory_s:.4f} | "
            f"{r.collective_s:.4f} | **{r.dominant}** | "
            f"{r.useful_flops_ratio:.2f} | {r.mfu_bound:.1%} |")
    return "\n".join(lines)
