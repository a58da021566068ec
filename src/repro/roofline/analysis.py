"""Three-term roofline analysis from a compiled dry-run artifact.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

The peaks come from :mod:`repro.roofline.peaks` for the chip the program
is placed on; there is no default chip.

``cost_analysis()`` of an SPMD-partitioned executable reports per-device
FLOPs/bytes, so the formulas above are the per-chip version of the spec's
(global / (chips * bw)) — identical numbers.

collective_bytes is not in cost_analysis: we parse the partitioned HLO text
and sum the traffic of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, using per-op formulas on the (per-shard)
printed shapes:

    all-gather         ~ result_bytes           (ring, (K-1)/K ~ 1)
    reduce-scatter     ~ operand_bytes
    all-reduce         ~ 2 * operand_bytes      (RS + AG)
    all-to-all         ~ operand_bytes
    collective-permute ~ operand_bytes
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "u1": 1, "s1": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(?:%?[\w.\-]+)\s*=\s*(?:\(?)([a-z0-9\[\],{}() ]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device collective traffic by op kind from partitioned HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # avoid double counting async pairs
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        result_shapes, kind = m.group(1), m.group(2)
        result_bytes = _shape_bytes(result_shapes)
        # operand shapes: inside the parens after the op name
        paren = line[m.end():]
        operand_bytes = _shape_bytes(paren.split("),")[0] if ")," in paren else paren)
        if operand_bytes == 0:
            # operands printed as bare names (common): fall back to result
            operand_bytes = result_bytes
        if kind == "all-gather":
            out[kind] += result_bytes
        elif kind == "all-reduce":
            out[kind] += 2 * operand_bytes
        else:
            out[kind] += operand_bytes
        counts[kind] += 1
    out["_counts"] = counts
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_global: float
    useful_ratio: float
    collectives: dict

    def row(self):
        out = {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "model_flops_global": self.model_flops_global,
            "useful_ratio": self.useful_ratio,
        }
        if hasattr(self, "xla_raw"):
            out["xla_raw"] = self.xla_raw
        return out


def roofline_terms(cost, hlo_text, chips, model_flops_global, peaks) -> Roofline:
    """Roofline from ``cost_analysis()`` and HLO text against ``peaks``
    (a :class:`repro.roofline.peaks.Peaks`)."""
    peak_flops, hbm_bw, link_bw = peaks.flops, peaks.hbm_bw, peaks.link_bw
    flops = float(cost.get("flops", 0.0))
    # cost_analysis reports "bytes accessed" (HBM traffic proxy).
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    cbytes = float(sum(v for k, v in coll.items() if not k.startswith("_")))
    compute_s = flops / peak_flops
    memory_s = bytes_acc / hbm_bw
    collective_s = cbytes / link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    hlo_flops_global = flops * chips
    useful = model_flops_global / hlo_flops_global if hlo_flops_global > 0 else 0.0
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collective_bytes_per_device=cbytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_global=model_flops_global,
        useful_ratio=useful,
        collectives=coll,
    )


def analyze_compiled(compiled, chips, model_flops_global, peaks) -> Roofline:
    """Primary path: loop-aware HLO parse (see hlo_parse.py) — XLA's
    cost_analysis() counts while bodies once, which under-reports any
    scan-over-layers program by ~num_layers x. The raw cost_analysis values
    are attached for reference as ``xla_raw``. ``peaks``: the
    :class:`repro.roofline.peaks.Peaks` of the chip it is placed on."""
    from repro.roofline.hlo_parse import analyze_hlo

    text = compiled.as_text()
    totals = analyze_hlo(text)
    peak_flops, hbm_bw, link_bw = peaks.flops, peaks.hbm_bw, peaks.link_bw
    cbytes = float(sum(totals.coll.values()))
    compute_s = totals.flops / peak_flops
    memory_s = totals.bytes / hbm_bw
    collective_s = cbytes / link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    hlo_flops_global = totals.flops * chips
    useful = model_flops_global / hlo_flops_global if hlo_flops_global > 0 else 0.0
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    roof = Roofline(
        flops_per_device=totals.flops,
        bytes_per_device=totals.bytes,
        collective_bytes_per_device=cbytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_global=model_flops_global,
        useful_ratio=useful,
        collectives={**{k: float(v) for k, v in totals.coll.items()},
                     "_counts": {k: int(v) for k, v in totals.coll_counts.items()}},
    )
    roof.xla_raw = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes accessed": float(cost.get("bytes accessed", 0.0)),
    }
    return roof
