"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Hardware model: :data:`PEAKS`, one row per ``jax.Device.device_kind``.
A kind missing from the table is an error, never a default.

Terms (seconds, per chip, per step):
  compute    = HLO_FLOPs / peak FLOP/s
  memory     = HLO_bytes / HBM bytes/s
  collective = collective_bytes / ICI bytes/s

HLO_FLOPs / HLO_bytes / collective_bytes are per-chip values from the
loop-aware analyzer (launch.hlo_analysis) over the SPMD-partitioned module;
the global value is chips x per-chip, so these terms equal the assignment's
``global / (chips * peak)`` formulation.

MODEL_FLOPS = 6*N*D (train; dense N or active N for MoE) or 2*N*D
(inference) — the "useful math" floor.  ``useful_fraction`` =
MODEL_FLOPS-ideal-time / max(term): how close the step is to running the
useful math at the roofline.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional


class Peaks(NamedTuple):
    flops: float        # dense bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # chip-to-chip interconnect bytes/s per chip


#: Published per-chip peaks keyed by ``device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
#: ICI).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8),
}

#: the chip the dry-run's placeholder meshes stand for (v5e pods)
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of one chip kind; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * chips)
    useful_fraction: float       # model-ideal-time / bound
    bound_s: float
    collective_breakdown: Dict[str, float]
    note: str = ""

    @property
    def key(self):
        return (self.arch, self.shape, self.mesh)


def analyze_cell(res: dict,
                 device_kind: str = DRYRUN_DEVICE_KIND) -> Optional[RooflineRow]:
    if res.get("skipped") or res.get("error"):
        return None
    peak = peaks_for(device_kind)
    hlo = res["hlo"]
    chips = res["chips"]
    compute_s = hlo["flops"] / peak.flops
    memory_s = hlo["traffic_bytes"] / peak.hbm_bw
    collective_s = hlo["collective_bytes_total"] / peak.ici_bw

    n = res["params_active"]
    d = res["tokens_per_step"]
    model_flops = (6.0 if res["step"] == "train" else 2.0) * n * d
    hlo_global = hlo["flops"] * chips
    bound = max(compute_s, memory_s, collective_s)
    ideal = model_flops / chips / peak.flops
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return RooflineRow(
        arch=res["arch"], shape=res["shape"],
        mesh="x".join(map(str, res["mesh"])), chips=chips,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops, hlo_flops=hlo_global,
        useful_ratio=model_flops / max(hlo_global, 1.0),
        useful_fraction=ideal / max(bound, 1e-30), bound_s=bound,
        collective_breakdown={k: v / peak.ici_bw
                              for k, v in hlo["collective_bytes"].items()},
    )


def load_rows(directory: str) -> List[RooflineRow]:
    rows = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            res = json.load(f)
        row = analyze_cell(res)
        if row:
            rows.append(row)
    return rows


def format_table(rows: List[RooflineRow]) -> str:
    hdr = (f"{'arch':<20} {'shape':<12} {'mesh':<9} "
           f"{'compute_s':>10} {'memory_s':>10} {'collect_s':>10} "
           f"{'bound':<10} {'useful':>7} {'frac':>6}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:<20} {r.shape:<12} {r.mesh:<9} "
            f"{r.compute_s:>10.4f} {r.memory_s:>10.4f} {r.collective_s:>10.4f} "
            f"{r.dominant:<10} {r.useful_ratio:>7.3f} {r.useful_fraction:>6.3f}")
    return "\n".join(lines)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    args = ap.parse_args()
    rows = load_rows(args.dir)
    print(format_table(rows))


if __name__ == "__main__":
    main()
