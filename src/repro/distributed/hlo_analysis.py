"""Post-compile HLO analysis: collective traffic + roofline terms.

``cost_analysis`` gives HLO FLOPs and bytes, but not collective traffic —
we parse the optimized (SPMD-partitioned, per-device) HLO text and sum the
operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, applying ring-algorithm wire factors:

  all-reduce      2·(n−1)/n · bytes
  all-gather        (n−1)/n · result bytes
  reduce-scatter    (n−1)   · result bytes   (input = n·result)
  all-to-all        (n−1)/n · bytes
  collective-permute        bytes

Hardware model: ``PEAKS``, keyed by ``device_kind`` as JAX reports it.  A
device missing from the table is an error, never a default.  The
collective term conservatively charges all traffic to ONE link (a
2D-torus chip has more); the roofline table notes this.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# Per-chip peaks.  TPU v5e: Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect (~50 GB/s per link over four links).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2  # unknown grouping: assume minimal


def _wire_factor(op: str, n: int) -> float:
    if op == "all-reduce":
        return 2 * (n - 1) / n
    if op == "all-gather":
        return (n - 1) / n
    if op == "reduce-scatter":
        return float(n - 1)
    if op == "all-to-all":
        return (n - 1) / n
    return 1.0  # collective-permute


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    result_bytes: dict
    wire_bytes: dict

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    def to_dict(self) -> dict:
        return {"counts": self.counts, "result_bytes": self.result_bytes,
                "wire_bytes": self.wire_bytes,
                "total_wire_bytes": self.total_wire_bytes}


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts = {c: 0 for c in _COLLECTIVES}
    res_bytes = {c: 0 for c in _COLLECTIVES}
    wire = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        lhs, _, rhs = ls.partition("=")
        rhs = rhs.strip()
        op = None
        for c in _COLLECTIVES:
            # match "bf16[...] all-reduce(" or "(f32[..]) all-reduce-start("
            if re.search(rf"\b{c}(-start)?\(", rhs):
                op = c
                break
        if op is None:
            continue
        if f"{op}-done" in rhs:
            continue  # avoid double counting async pairs
        # result shape(s) = text before the op name
        seg = rhs.split(op)[0]
        nbytes = _shape_bytes(seg)
        n = _group_size(rhs)
        counts[op] += 1
        res_bytes[op] += nbytes
        wire[op] += nbytes * _wire_factor(op, n)
    return CollectiveStats(counts=counts, result_bytes=res_bytes,
                           wire_bytes=wire)


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   wire_bytes_per_device: float,
                   model_flops_per_device: float = 0.0, *,
                   device_kind: str) -> dict:
    """Three roofline terms in seconds (per assignment formulae) plus:

    * ``useful_ratio``  = MODEL_FLOPS / HLO_FLOPs  (remat / redundancy waste)
    * ``mfu_bound``     = model-flops-time / max(term): the best MFU this
      compiled program could reach if the dominant term ran at peak — the
      static-analysis stand-in for measured MFU (CPU-only container).
    """
    pk = peaks(device_kind)
    compute_s = flops_per_device / pk["flops"]
    memory_s = bytes_per_device / pk["hbm_bw"]
    collective_s = wire_bytes_per_device / pk["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    model_s = model_flops_per_device / pk["flops"]
    return {**terms, "dominant": dominant, "roofline_s": bound,
            "model_flops_s": model_s,
            "useful_ratio": (model_flops_per_device / flops_per_device
                             if flops_per_device else 0.0),
            "mfu_bound": (model_s / bound) if bound else 0.0}


def cost_summary(compiled, hlo_text: Optional[str] = None) -> dict:
    """Extract flops/bytes from compiled.cost_analysis() + collectives."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    ca = dict(ca or {})
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)
    return {"flops": flops, "bytes_accessed": bytes_accessed,
            "collectives": coll.to_dict(), "memory": mem,
            "transcendentals": float(ca.get("transcendentals", 0.0))}
