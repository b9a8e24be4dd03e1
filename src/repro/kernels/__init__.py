"""Pallas TPU kernels for the compute hot-spot the paper optimizes:
the yCHG column scan (step 1) and neighbour diff (step 2).

These kernels are *backends*, not entry points: the canonical public API is
``repro.engine.Engine``, where they register as ``"fused"`` (single
launch, batched, mesh-capable) and ``"pallas"`` (two-pass) with capability
flags that drive ``backend="auto"`` dispatch. Call
``Engine(YCHGConfig(backend="fused")).analyze_batch(stack)`` rather
than ``ops.analyze_fused`` directly — the engine keeps results
device-resident, applies the VMEM streaming threshold from its config, and
composes with batch sharding (a mesh attached to the engine shard_maps the
fused backend). See ``repro.engine`` for the migration table.

  ychg_colscan.py  two-pass pl.pallas_call kernels + BlockSpec VMEM tiling
                   (one launch per step, HBM round-trip for the counts)
  ychg_fused.py    fused batched pipeline: BOTH steps for a (B, H, W) stack
                   in ONE launch — step 2's diff computed in-register from
                   step 1's tile result, with a (1, bw) VMEM carry for the
                   tile seam and revisited accumulator blocks for per-image
                   totals; streamed variant adds an H-tile grid dim with a
                   carry row for images past the VMEM budget
  ychg_packed.py   1-bit row packing (8x less HBM traffic on the scan)
  ccl.py, denoise.py  whole-image kernels of the other ops
  ingest.py        a host byte mask shipped as 32-bit words and unpacked
                   on the device (the engine's ingest of host masks)
  platform.py      interpret-or-compile from the platform, in one place;
                   scoped-VMEM limits for the whole-image kernels
  ops.py           jit'd wrappers;
                   ``analyze_fused`` returns a core.ychg.YCHGSummary,
                   bit-identical to core.ychg.analyze
  ref.py           pure-jnp oracles for the exact-equality sweeps

Fused-vs-two-pass, measured (CPU, Pallas interpret mode; benchmarks/run.py
``bench_fused_batch_sweep``, us/call):

  batch x res   fused (1 launch)  two-pass (2B launches)  fused gain
  1  x 128        265               653                    2.46x
  8  x 128        505              1523                    3.02x
  32 x 128       1818              8475                    4.66x
  8  x 256       1138              2518                    2.21x

The gain grows with batch size exactly as the paper's data-parallel claim
predicts — launch/dispatch overhead amortises over the batch. At large
B*H*W (e.g. 32 x 512) interpret mode inverts the curve: each grid step is
evaluated in Python, so per-step overhead dominates and the two-pass
pipeline's smaller per-step blocks win. That inversion is an artifact of
interpret mode only; on a compiled TPU backend the fused kernel strictly
removes one launch per image, one HBM round-trip of the (W,) counts vector,
and one shifted HBM copy. The pure-jnp path (core.ychg) stays the fastest
on this CPU-only box and remains the production default there; the fused
kernel is the TPU path and the launch-count ledger above is its contract.
"""

from repro.kernels import ops, ref
from repro.kernels.ops import analyze_fused

__all__ = ["ops", "ref", "analyze_fused"]
