"""The paper's own workload: yCHG over MODIS-like scenes.

Knobs mirror the poster's experiments: resolution series up to the
21000x21000 scene (knob a) and hyperedge series 147 -> 4,124,319 (knob b).
The ``engine`` section is the canonical way this workload constructs its
yCHG computation: ``Engine(config().engine.to_engine_config())``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class EngineSection:
    """Mirror of ``repro.engine.YCHGConfig`` inside the workload config.

    Kept as plain data (no repro.engine import at config-definition time)
    so configs stay importable in tooling that never runs the algorithm.
    """

    backend: str = "auto"              # registry-resolved per platform
    block_w: int = 128                 # Pallas lane tile
    block_h: int = 2048                # streamed kernel row tile
    dtype: Optional[str] = None        # cast masks on ingest (None = as-is)
    mesh_axis: str = "data"            # batch axis when a mesh is attached
    interpret: Optional[bool] = None   # None = interpret off-TPU
    stream_vmem_budget: int = 1024 * 1024

    def to_engine_config(self, **overrides: Any):
        """Materialise as a ``repro.engine.YCHGConfig`` (with overrides)."""
        from repro.engine import YCHGConfig

        kw = dataclasses.asdict(self)
        kw.update(overrides)
        return YCHGConfig(**kw)


@dataclasses.dataclass(frozen=True)
class YCHGWorkloadConfig:
    name: str = "ychg-modis"
    resolutions: Tuple[int, ...] = (250, 500, 1000, 2000, 4000, 8000, 12000, 21000)
    hyperedge_series: Tuple[int, ...] = (
        147, 1_000, 10_000, 100_000, 1_000_000, 4_124_319
    )
    hyperedge_resolution: int = 8192   # fixed resolution for knob (b)
    batch: int = 8                     # tiles per device batch in the pipeline
    engine: EngineSection = EngineSection()
    backends: Tuple[str, ...] = ("scalar", "serial", "jax", "pallas", "fused")

    # legacy flat tile knobs, kept as views of the engine section
    @property
    def block_w(self) -> int:
        return self.engine.block_w

    @property
    def block_h(self) -> int:
        return self.engine.block_h


def config() -> YCHGWorkloadConfig:
    return YCHGWorkloadConfig()
