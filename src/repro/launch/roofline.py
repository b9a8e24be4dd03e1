"""Roofline accounting from the compiled dry-run artifact.

Peak rates sit in ``PEAKS``, keyed by JAX's ``device_kind``; a device that
is not in the table is an error, never a default. The dry-run models its
target chip, ``TARGET_DEVICE_KIND`` (TPU v5e), and charges collectives ONE
ICI link (conservative: v5e has 4 usable links, so a perfect schedule could
be ~4x better).

Collective bytes are parsed from the *optimized* HLO of the compiled module:
operands are not typed inline in current HLO dumps, so per-op ICI traffic is
derived from the RESULT shape with standard ring-algorithm multipliers and
the parsed replica-group size g:

  all-gather          result x (g-1)/g        (per-device recv bytes)
  all-reduce          result x 2(g-1)/g       (reduce-scatter + all-gather)
  reduce-scatter      result x (g-1)          (operand = result x g)
  all-to-all          result x (g-1)/g
  collective-permute  result x 1              (one hop send/recv)

cost_analysis() counts while-loop bodies ONCE (not x trip count), so the
dry-run measures collectives with two unrolled reduced-depth probe compiles
(G=1, G=2 layer groups) and extrapolates: per_group = m(2) - m(1);
total(G) = m(1) - per_group + G*per_group. Probes compile in f32 (XLA CPU
upcasts bf16 dots, which would inflate weight-collective bytes); float
collective results are therefore counted at bf16 width (ints at native
width) to model the TPU execution. FLOPs/HBM bytes for train/prefill cells
come from the analytic model in launch/analytic.py (inner attention/ssm
chunk loops are also while loops, invisible to cost_analysis); decode cells
have no inner loops, so extrapolated measurements are used and the analytic
model is cross-checked against them.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peak rates."""

    flops: float      # bf16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    ici_bw: float     # bytes/s per ICI link


# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """Peak rates of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates recorded for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None


def cost_dict(compiled) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` as a dict ({} when XLA reports none);
    dryrun and the tests parse the artifact through this one function."""
    return compiled.cost_analysis() or {}


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}
_FLOAT_TYPES = {"f16", "bf16", "f32", "f64"}

_COLL_TYPES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# `%name = TYPE[dims]{layout} op-name(...`  (also tuple-result async starts)
_INSTR_RE = re.compile(
    r"=\s*(?:\(?)\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, *, clamp_float_to_bf16: bool = True,
                     default_group: int = 16) -> Dict[str, float]:
    """Per-partition ICI traffic (bytes) by collective type, + op counts."""
    out: Dict[str, float] = {t: 0.0 for t in _COLL_TYPES}
    counts: Dict[str, int] = {t: 0 for t in _COLL_TYPES}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        dtype, dims, op, _start = m.group(1), m.group(2), m.group(3), m.group(4)
        if dtype not in _DTYPE_BYTES:
            continue
        if f"{op}-done" in line:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        width = _DTYPE_BYTES[dtype]
        if clamp_float_to_bf16 and dtype in _FLOAT_TYPES:
            width = min(width, 2)
        bytes_result = n * width
        g = _group_size(line, default_group)
        if op == "all-gather":
            traffic = bytes_result * (g - 1) / g
        elif op == "all-reduce":
            traffic = bytes_result * 2 * (g - 1) / g
        elif op == "reduce-scatter":
            traffic = bytes_result * (g - 1)
        elif op == "all-to-all":
            traffic = bytes_result * (g - 1) / g
        else:  # collective-permute
            traffic = bytes_result
        out[op] += traffic
        counts[op] += 1
    out["total"] = sum(out[t] for t in _COLL_TYPES)
    for t in _COLL_TYPES:
        out["_count_" + t] = counts[t]
    return out


def extrapolate(m1: Dict[str, float], m2: Dict[str, float], g: int
                ) -> Dict[str, float]:
    """Linear trip-count correction from G=1 / G=2 unrolled probes."""
    out = {}
    for k in m1:
        per_group = m2.get(k, 0.0) - m1.get(k, 0.0)
        base = m1.get(k, 0.0) - per_group
        out[k] = base + g * per_group
    return out


def terms(
    *,
    flops_global: float,
    bytes_global: float,
    coll_bytes_per_partition: float,
    n_partitions: int,
) -> Dict[str, float]:
    chips = n_partitions
    cg = coll_bytes_per_partition * n_partitions
    peak = peaks(TARGET_DEVICE_KIND)
    return {
        "flops_global": flops_global,
        "bytes_global": bytes_global,
        "coll_bytes_global": cg,
        "compute_s": flops_global / (chips * peak.flops),
        "memory_s": bytes_global / (chips * peak.hbm_bw),
        "collective_s": cg / (chips * peak.ici_bw),
    }


def model_flops(cfg, shape) -> float:
    """6*N_eff*D (train) / 2*N_eff*D (prefill/decode): the useful-work floor.

    N_eff = active params minus the embedding lookup table when untied
    (lookup is a gather, not a matmul; a tied table doubles as the lm_head
    matmul so it stays).
    """
    from repro.models import active_params

    n = active_params(cfg)
    if not cfg.tie_embeddings:
        n -= cfg.vocab_size * cfg.d_model
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token


def dominant(t: Dict[str, float]) -> str:
    vals = {k: t[k] for k in ("compute_s", "memory_s", "collective_s")}
    return max(vals, key=vals.get)
