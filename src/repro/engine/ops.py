"""Operator registry: what the engine can compute, beyond which backend.

The backend registry (``repro.engine.registry``) answers "which
implementation of op X runs here"; this module answers "what IS op X" —
its result pytree, its in-repo reference (the parity bar every backend is
held to), and how it composes into device-resident pipelines:

  * ``fields``      — the result's array fields, all leading with the batch
                      dim (so the generic shard_map mesh path in the engine
                      works for every op);
  * ``result_type`` / ``from_summary`` — the frozen pytree wrapper;
  * ``reference``   — jnp reference over a (B, H, W) stack; backends must
                      be bit-identical to it (tests enforce this);
  * ``chain_field`` — the result field fed to the next stage of a pipeline
                      spec (None = terminal op: it cannot appear mid-chain).

``docs/ops.md`` walks through adding a new op end to end.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ychg as _ychg
from repro.engine.registry import UnknownOpError
from repro.kernels import ccl as _ccl
from repro.kernels import denoise as _denoise

Array = jax.Array

__all__ = [
    "CCLResult",
    "DenoiseResult",
    "OpSpec",
    "get_op",
    "op_names",
    "register_op",
    "pipeline_op_key",
    "split_pipeline_key",
]

# Separator for pipeline cache/bucket keys ("denoise+ychg"); op names must
# therefore never contain it (register_op validates).
PIPELINE_SEP = "+"


@dataclasses.dataclass(frozen=True)
class CCLResult:
    """Device-resident batched connected-components labeling output."""

    labels: Array        # (B, H, W) int32 canonical labels, 0 = background
    n_components: Array  # (B,) int32
    batched: bool = dataclasses.field(default=True,
                                      metadata=dict(static=True))
    # the band path's (B, 2) passes and merge rounds (``CCLSummary.diag``);
    # not a result field: ``to_host()`` puts it on the ``engine.fetch`` span
    diag: Optional[Array] = None

    @property
    def batch_size(self) -> int:
        return self.labels.shape[0]

    def block_until_ready(self) -> "CCLResult":
        jax.block_until_ready((self.labels, self.n_components))
        return self

    def to_summary(self) -> _ccl.CCLSummary:
        if self.batched:
            return _ccl.CCLSummary(self.labels, self.n_components)
        return _ccl.CCLSummary(self.labels[0], self.n_components[0])

    def to_host(self) -> Dict[str, np.ndarray]:
        from repro.engine.engine import fetch

        return fetch(self, _ccl.CCL_FIELDS)


@dataclasses.dataclass(frozen=True)
class DenoiseResult:
    """Device-resident batched P-HGRMS denoise output."""

    image: Array  # (B, H, W) float32
    batched: bool = dataclasses.field(default=True,
                                      metadata=dict(static=True))

    @property
    def batch_size(self) -> int:
        return self.image.shape[0]

    def block_until_ready(self) -> "DenoiseResult":
        jax.block_until_ready(self.image)
        return self

    def to_summary(self) -> _denoise.DenoiseSummary:
        if self.batched:
            return _denoise.DenoiseSummary(self.image)
        return _denoise.DenoiseSummary(self.image[0])

    def to_host(self) -> Dict[str, np.ndarray]:
        from repro.engine.engine import fetch

        return fetch(self, _denoise.DENOISE_FIELDS)


jax.tree_util.register_dataclass(
    CCLResult, data_fields=["labels", "n_components", "diag"],
    meta_fields=["batched"]
)
jax.tree_util.register_dataclass(
    DenoiseResult, data_fields=["image"], meta_fields=["batched"]
)


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One operator the engine can dispatch."""

    name: str
    fields: Tuple[str, ...]
    result_type: type
    summary_type: type            # field-ordered summary (mesh repack)
    from_summary: Callable        # (summary, batched: bool) -> result
    reference: Callable           # (B, H, W) stack -> summary (parity bar)
    chain_field: Optional[str] = None  # pipeline output field; None = terminal


_OPS: Dict[str, OpSpec] = {}


def register_op(spec: OpSpec) -> OpSpec:
    if PIPELINE_SEP in spec.name:
        raise ValueError(
            f"op name {spec.name!r} may not contain {PIPELINE_SEP!r} "
            "(reserved for pipeline keys)"
        )
    _OPS[spec.name] = spec
    return spec


def op_names() -> Tuple[str, ...]:
    return tuple(sorted(_OPS))


def get_op(name: str) -> OpSpec:
    try:
        return _OPS[name]
    except KeyError:
        raise UnknownOpError(
            f"unknown op {name!r}; registered ops: {op_names()}"
        ) from None


def pipeline_op_key(stages: Tuple[str, ...]) -> str:
    """Ordered stage names -> the op-qualified key used by cache/buckets."""
    return PIPELINE_SEP.join(stages)


def split_pipeline_key(op_key: str) -> Tuple[str, ...]:
    return tuple(op_key.split(PIPELINE_SEP))


def validate_pipeline(stages) -> Tuple[str, ...]:
    """Check an ordered pipeline spec: known ops, chainable interiors."""
    stages = tuple(stages)
    if not stages:
        raise ValueError("pipeline spec needs at least one op stage")
    for s in stages:
        get_op(s)  # raises UnknownOpError with the registered list
    for s in stages[:-1]:
        if get_op(s).chain_field is None:
            raise ValueError(
                f"op {s!r} is terminal (no chain_field) and cannot feed a "
                f"later pipeline stage"
            )
    return stages


# --------------------------------------------------------------- built-ins

def _ychg_from_summary(s, batched: bool):
    from repro.engine.engine import _from_summary

    return _from_summary(s, batched)


def _ychg_result_type():
    from repro.engine.engine import YCHGResult

    return YCHGResult


register_op(OpSpec(
    name="ychg",
    fields=("runs", "cut_vertices", "transitions", "births", "deaths",
            "n_hyperedges", "n_transitions"),
    summary_type=_ychg.YCHGSummary,
    # resolved lazily below to avoid a circular import at module load
    result_type=object,
    from_summary=_ychg_from_summary,
    reference=_ychg.analyze,
    chain_field=None,   # (B, W) outputs: not an image, cannot feed a stage
))

register_op(OpSpec(
    name="ccl",
    fields=("labels", "n_components"),
    summary_type=_ccl.CCLSummary,
    result_type=CCLResult,
    from_summary=lambda s, batched: CCLResult(
        labels=s.labels, n_components=s.n_components, batched=batched,
        diag=s.diag),
    reference=_ccl.labels,
    chain_field="labels",   # nonzero labels = foreground downstream
))

register_op(OpSpec(
    name="denoise",
    fields=("image",),
    summary_type=_denoise.DenoiseSummary,
    result_type=DenoiseResult,
    from_summary=lambda s, batched: DenoiseResult(image=s.image,
                                                  batched=batched),
    reference=_denoise.denoise,
    chain_field="image",
))


def _finalize_ychg_result_type() -> None:
    """Called by ``repro.engine`` once ``engine.engine`` is importable."""
    spec = _OPS["ychg"]
    if spec.result_type is object:
        _OPS["ychg"] = dataclasses.replace(
            spec, result_type=_ychg_result_type())
