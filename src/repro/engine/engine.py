"""`YCHGConfig` / `YCHGResult` / `Engine` — the unified entry point.

One engine instance owns one dispatch policy (backend selection, Pallas tile
sizes, streaming threshold, optional device mesh) over every registered
*operator* — yCHG first, plus ``ccl`` and ``denoise`` — and exposes three
verbs (each takes ``op=`` to override the engine's default op per call):

  * ``analyze(img)``         — one (H, W) mask; internally a B=1 view of the
                               batched path, NOT a separate code path;
  * ``analyze_batch(stack)`` — a (B, H, W) stack in one device computation;
  * ``analyze_stream(it)``   — an iterable of masks/stacks, one result
                               yielded per item;

plus ``run_pipeline(stack, stages)``: an ordered op chain executed
device-resident end to end — each stage's output feeds the next with no
host round trip, bit-identical to issuing the stages as separate calls.

Every verb returns the op's result pytree (``YCHGResult`` for yCHG — see
``repro.engine.ops`` for the others): ``jax.tree_util``-registered device
arrays that can cross ``jit``/``shard_map`` boundaries and never leave the
device implicitly. ``.to_host()`` produces the legacy host dict.

Ingest: a C-contiguous uint8/int8 host mask of at least
``ingest.MIN_BYTES`` whose width is a multiple of 4 is shipped as 32-bit
words and restored on the device (``repro.kernels.ingest``), the same
array in dtype, shape and bytes; every other input is shipped as it is.

Tracing: a host input's transfer is an ``engine.put`` span (meta
``words``: 1 when it went as words), each backend run an
``engine.dispatch`` span, and every ``to_host()`` an ``engine.fetch``
span. They join the trace the calling tier made current
(``repro.obs.use_trace``), or each opens and finishes a trace of its own.

``YCHGEngine`` remains as a deprecation shim over ``Engine`` (same policy,
op pinned to ``"ychg"``), mirroring the PR 2 treatment of
``core.api.analyze_image``.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, Iterable, Iterator, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.ychg import YCHGSummary
from repro.engine import registry
from repro.kernels import ingest
from repro.obs import join_trace

Array = jax.Array

_FIELDS = ("runs", "cut_vertices", "transitions", "births", "deaths",
           "n_hyperedges", "n_transitions")


@dataclasses.dataclass(frozen=True)
class YCHGConfig:
    """Frozen, hashable engine construction knobs (shared by every op).

    backend            "auto" resolves per (op, platform) from the registry;
                       or any name registered for the engine's op
                       ("jax", "fused", "pallas", "serial", "scalar" for
                       ychg; "jax"/"pallas" for ccl and denoise).
    block_w, block_h   Pallas lane / streamed-row tile sizes.
    dtype              optional dtype name masks are cast to on ingest
                       (None = accept as-is; nonzero = foreground either way).
    mesh_axis          batch axis name used when a mesh is attached.
    interpret          Pallas interpret flag (None = auto: interpret off-TPU;
                       True is refused on a TPU, where the kernels compile).
    stream_vmem_budget raw-tile bytes past which the fused/colscan kernels
                       switch to the H-streamed variant (VMEM threshold).
    """

    backend: str = "auto"
    block_w: int = 128
    block_h: int = 2048
    dtype: Optional[str] = None
    mesh_axis: str = "data"
    interpret: Optional[bool] = None
    stream_vmem_budget: int = 1024 * 1024


# the knobs are op-agnostic; EngineConfig is the preferred spelling going
# forward, YCHGConfig the historical one (both are the same class)
EngineConfig = YCHGConfig


@dataclasses.dataclass(frozen=True)
class YCHGResult:
    """Device-resident batched output of the two-step algorithm.

    Arrays always carry the leading batch dim — a single image is a B=1
    view. Registered with ``jax.tree_util`` (the ``batched`` flag is static
    aux data), so results flow through ``jit``/``vmap``/``tree_map``
    untouched. Nothing is copied to the host until ``to_host()``.
    """

    runs: Array           # (B, W) int32  step-1 per-column run counts
    cut_vertices: Array   # (B, W) int32  2*runs
    transitions: Array    # (B, W) bool   step-2 change signal
    births: Array         # (B, W) int32
    deaths: Array         # (B, W) int32
    n_hyperedges: Array   # (B,)   int32  total births
    n_transitions: Array  # (B,)   int32  number of transition columns
    batched: bool = dataclasses.field(default=True, metadata=dict(static=True))

    @property
    def batch_size(self) -> int:
        return self.runs.shape[0]

    def block_until_ready(self) -> "YCHGResult":
        jax.block_until_ready(tuple(getattr(self, f) for f in _FIELDS))
        return self

    def to_summary(self) -> YCHGSummary:
        """``core.ychg.YCHGSummary`` view (squeezed to (W,)/() for B=1 input)."""
        if self.batched:
            return YCHGSummary(*(getattr(self, f) for f in _FIELDS))
        return YCHGSummary(*(getattr(self, f)[0] for f in _FIELDS))

    def to_host(self) -> Dict[str, np.ndarray]:
        """The legacy ``core.api.analyze_image`` dict: host NumPy values."""
        return fetch(self, _FIELDS)


jax.tree_util.register_dataclass(
    YCHGResult, data_fields=list(_FIELDS), meta_fields=["batched"]
)


def fetch(result: Any, fields: Sequence[str]) -> Dict[str, np.ndarray]:
    """Every op result's ``to_host()``: the wait for the device, the
    squeeze of a B=1 view and the device->host copy, under one
    ``engine.fetch`` span (meta: the result's device bytes). A result
    that carries ``diag`` (the band path of ``ccl``) brings it back in
    the same copy and puts its largest passes, merge rounds and distinct
    seam labels over the batch on the span as ``sweeps``,
    ``merge_rounds`` and ``seam_labels``."""
    diag = getattr(result, "diag", None)
    with join_trace("engine") as tr, tr.span("engine.fetch", bytes=sum(
            getattr(result, f).nbytes for f in fields)) as sp:
        s = result.to_summary()
        if diag is None:
            return {f: np.asarray(getattr(s, f)) for f in fields}
        *host, counts = jax.device_get([getattr(s, f) for f in fields]
                                       + [diag])
        if tr.enabled:
            sweeps, rounds, seam_labels = counts.max(axis=0).tolist()
            sp.meta.update(sweeps=sweeps, merge_rounds=rounds,
                           seam_labels=seam_labels)
        return dict(zip(fields, host))


def _from_summary(s: YCHGSummary, batched: bool) -> YCHGResult:
    # hot-path constructor: fills __dict__ directly instead of going through
    # the frozen-dataclass __init__ (8 object.__setattr__ calls) — this sits
    # inside the engine's <=5us/call dispatch-overhead budget
    r = object.__new__(YCHGResult)
    d = r.__dict__
    d["runs"] = s.runs
    d["cut_vertices"] = s.cut_vertices
    d["transitions"] = s.transitions
    d["births"] = s.births
    d["deaths"] = s.deaths
    d["n_hyperedges"] = s.n_hyperedges
    d["n_transitions"] = s.n_transitions
    d["batched"] = batched
    return r


def _word_view(host: np.ndarray) -> Optional[np.ndarray]:
    """``host`` as (B*H, W/4) uint32 words, a free view, when it is a
    C-contiguous uint8/int8 (B, H, W) stack of at least
    ``ingest.MIN_BYTES`` whose width is a multiple of 4; else None (the
    array is shipped as it is)."""
    if (host.dtype in (np.uint8, np.int8) and host.ndim == 3
            and host.nbytes >= ingest.MIN_BYTES and host.shape[-1] % 4 == 0
            and host.flags.c_contiguous):
        return host.reshape(-1, host.shape[-1]).view(np.uint32)
    return None


def _zero_pad_region(x: Array, valid_hw: Array) -> Array:
    """Zero rows >= h and cols >= w per image (valid_hw: (B, 2) int32).

    Between pipeline stages this restores the exact canvas a single-op
    submit would see — a stage may write nonzero values into the pad
    region (denoise's RMS does, next to native pixels), and the next stage
    must not observe them. h/w stay traced, so one compiled pipeline
    serves every ragged batch of a bucket shape.
    """
    _, h, w = x.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)[None]
    cols = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)[None]
    keep = (rows < valid_hw[:, 0, None, None]) & (
        cols < valid_hw[:, 1, None, None])
    return jnp.where(keep, x, jnp.zeros((), x.dtype))


class Engine:
    """The sole dispatch point for image-operator computations.

    ``Engine()`` (all defaults) serves the ``ychg`` op, resolving the best
    backend per call; ``Engine(op="ccl")`` pins a different default op, and
    every verb accepts ``op=`` for per-call override. Attach a device mesh
    with ``with_mesh`` to batch-shard any batch-capable backend over it
    (padding to the mesh size and stripping the pad internally, so callers
    never see padded-length results).
    """

    def __init__(self, config: YCHGConfig = YCHGConfig(), *,
                 op: str = "ychg", mesh: Optional[Mesh] = None):
        if mesh is not None and config.mesh_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}, config.mesh_axis="
                f"{config.mesh_axis!r}"
            )
        # platform is fixed per process; cache it out of the hot dispatch path
        self._platform = jax.default_backend()
        if config.interpret and self._platform == "tpu":
            raise ValueError(
                "interpret=True on a TPU would evaluate the Pallas kernels in "
                "Python instead of compiling them; leave interpret=None")
        self.config = config
        self.op = op
        self.mesh = mesh
        self._cast_dtype = None if config.dtype is None else jnp.dtype(config.dtype)
        # op -> (registry generation, resolved spec) — revalidated against
        # registry.generation() so late register_backend() calls still apply
        self._spec_cache: Dict[str, tuple[int, registry.BackendSpec]] = {}

    # ------------------------------------------------------------- plumbing

    def with_mesh(self, mesh: Optional[Mesh]) -> "Engine":
        """Same policy, batch-sharded over ``mesh`` (None detaches)."""
        return Engine(self.config, op=self.op, mesh=mesh)

    def with_config(self, **overrides: Any) -> "Engine":
        """New engine with ``dataclasses.replace``d config, same op/mesh."""
        return Engine(dataclasses.replace(self.config, **overrides),
                      op=self.op, mesh=self.mesh)

    def resolve_backend(self, op: Optional[str] = None) -> str:
        """Name of the backend this engine dispatches ``op`` to right now."""
        return self._resolve(op or self.op).name

    def _resolve(self, op: str) -> registry.BackendSpec:
        gen = registry.generation()
        cached = self._spec_cache.get(op)
        if cached is not None and cached[0] == gen:
            return cached[1]
        spec = registry.resolve(
            self.config.backend,
            platform=self._platform,
            need_mesh=self.mesh is not None,
            op=op,
        )
        self._spec_cache[op] = (gen, spec)
        return spec

    def _opspec(self, op: str):
        from repro.engine import ops as engine_ops

        return engine_ops.get_op(op)

    def _ingest(self, imgs: Any, *, single: bool = False) -> Array:
        # device arrays pass through untouched: no host round-trip, and no
        # jnp.asarray no-op either (it costs ~17us/call of pure dispatch —
        # the engine's <=5us/call overhead budget lives or dies here).
        # ``single``: a lone (H, W) mask, given its batch axis here
        if isinstance(imgs, jax.Array):
            x = imgs[None] if single else imgs
        else:
            host = np.asarray(imgs)
            if single:
                host = host[None]
            words = _word_view(host)
            # the runtime's relayout and the copy it starts; for a byte
            # mask shipped as words (meta words=1) also the unpack's dispatch
            with join_trace("engine") as tr, tr.span(
                    "engine.put", bytes=host.nbytes,
                    words=int(words is not None)):
                if words is None:
                    x = jnp.asarray(host)
                else:
                    x = ingest.ship(words, host.shape[0], host.dtype,
                                    interpret=self.config.interpret)
        if self._cast_dtype is not None and x.dtype != self._cast_dtype:
            x = x.astype(self._cast_dtype)
        return x

    # ------------------------------------------------------------- dispatch

    def analyze(self, img: Any, *, op: Optional[str] = None):
        """One (H, W) mask -> B=1 result (never copies device->host)."""
        if np.ndim(img) != 2:
            raise ValueError(f"analyze expects an (H, W) mask, got "
                             f"{np.shape(img)}; use analyze_batch for stacks")
        x = self._ingest(img, single=True)
        return self._run(x, batched=False, op=op or self.op)

    def analyze_batch(self, stack: Any, *, op: Optional[str] = None):
        """A (B, H, W) stack in one device computation."""
        x = self._ingest(stack)
        if x.ndim != 3:
            raise ValueError(f"analyze_batch expects a (B, H, W) stack, "
                             f"got {x.shape}")
        return self._run(x, batched=True, op=op or self.op)

    def analyze_stream(self, items: Iterable[Any], *,
                       op: Optional[str] = None) -> Iterator[Any]:
        """Lazily map ``analyze``/``analyze_batch`` over an iterable,
        double-buffering ingest against device compute.

        Each item may be an (H, W) mask or a (B, H, W) stack; one result is
        yielded per item, strictly in order. The stream runs one item ahead
        of the yield point: item n+1 is pulled from the iterator and its
        host->device transfer started *before* result n is yielded, so
        while the consumer handles result n (whose computation was
        dispatched asynchronously) the next item's host work and transfer
        are already in flight. Compose with ``data.pipeline.Prefetcher``
        for background host I/O.
        """
        run_op = op or self.op
        it = iter(items)
        pending = None
        while True:
            # pull and ingest (start the transfer of) item n+1 first ...
            try:
                item = next(it)
                batched = np.ndim(item) != 2
                x = self._ingest(item, single=not batched)
                if x.ndim != 3:
                    raise ValueError(
                        f"stream items must be (H, W) or (B, H, W), "
                        f"got {x.shape}"
                    )
            except StopIteration:
                break
            except Exception:
                # a bad item — or a source iterator that raises — must not
                # swallow the previous item's computed result: deliver it,
                # then raise on the consumer's next pull
                if pending is not None:
                    yield pending
                    pending = None
                raise
            # ... only then hand result n to the consumer, overlapping its
            # wait with the transfer above; dispatch n+1 when control returns
            if pending is not None:
                yield pending
            pending = self._run(x, batched=batched, op=run_op)
        if pending is not None:
            yield pending

    def run_pipeline(self, stack: Any, stages: Sequence[str], *,
                     valid_hw: Optional[Any] = None, batched: bool = True,
                     on_stage: Optional[Callable[[str, float, float],
                                                 None]] = None):
        """Execute an ordered op chain device-resident, no host round trips.

        Each stage's ``chain_field`` output becomes the next stage's input
        stack. ``valid_hw`` ((B, 2) int32 of per-image (h, w)) optionally
        re-zeroes the pad region between stages so a bucket-padded batch
        stays bit-identical to issuing the stages as separate (cropped)
        submits — see :func:`_zero_pad_region`. ``on_stage(name, t0, t1)``
        fires after each stage's (synchronous) dispatch — the service uses
        it to emit per-stage ``pipeline.<op>`` spans and stage histograms.
        Returns the LAST stage's result.
        """
        from repro.engine import ops as engine_ops

        stages = engine_ops.validate_pipeline(stages)
        x = self._ingest(stack)
        if x.ndim != 3:
            raise ValueError(
                f"run_pipeline expects a (B, H, W) stack, got {x.shape}")
        hw = None if valid_hw is None else jnp.asarray(valid_hw, jnp.int32)
        result = None
        for i, name in enumerate(stages):
            t0 = time.monotonic()
            result = self._run(x, batched=batched, op=name)
            if i + 1 < len(stages):
                x = getattr(result, self._opspec(name).chain_field)
                if hw is not None:
                    x = _zero_pad_region(x, hw)
            if on_stage is not None:
                on_stage(name, t0, time.monotonic())
        return result

    def _run(self, imgs: Array, *, batched: bool, op: str):
        opspec = self._opspec(op)
        spec = self._resolve(op)
        # counted BEFORE the run so a raising backend still shows up in
        # call_count; the dispatch-cost histogram only sees successes
        registry.note_call(spec.name, op)
        with join_trace("engine") as tr, tr.span(
                "engine.dispatch", backend=spec.name, op=op,
                px=imgs.size) as sp:
            t0 = time.monotonic()
            if self.mesh is not None:
                out = opspec.from_summary(
                    self._run_meshed(spec, opspec, imgs), batched)
            else:
                out = opspec.from_summary(spec.run(imgs, self.config),
                                          batched)
            t1 = time.monotonic()
            # the span and the dispatch histogram share these two reads
            sp.stamp(t0, t1)
        registry.note_dispatch(spec.name, t1 - t0, op)
        return out

    def _run_meshed(self, spec: registry.BackendSpec, opspec,
                    imgs: Array):
        """shard_map ``spec`` over the 1-D batch mesh.

        Ragged batches are padded with blank images (inert end to end for
        every op: zero pixels form no runs, no components, and denoise to
        zero) to a multiple of the mesh size and the pad is stripped before
        returning, so non-divisible batch sizes are invisible to callers.
        """
        from repro.sharding.ychg import pad_batch

        axis = self.config.mesh_axis
        x, b = pad_batch(imgs, self.mesh.shape[axis])
        cfg = self.config
        fields = opspec.fields

        def local(xs: Array):
            s = spec.run(xs, cfg)
            return tuple(getattr(s, f) for f in fields)

        pspec = P(axis)
        outs = jax.shard_map(local, mesh=self.mesh, in_specs=pspec,
                             out_specs=pspec, check_vma=False)(x)
        return opspec.summary_type(*(o[:b] for o in outs))

    # ------------------------------------------------------------ tooling

    def lower(self, stack_shape: tuple[int, int, int],
              dtype: Any = jnp.uint8, op: Optional[str] = None) -> Any:
        """jit-lower this engine's batched path for an abstract input shape.

        Used by ``launch.dryrun`` to prove a (backend x shape) cell lowers
        and compiles without allocating the stack.
        """
        run_op = op or self.op
        opspec = self._opspec(run_op)
        spec = self._resolve(run_op)
        cfg = self.config

        def run(x: Array):
            return opspec.from_summary(spec.run(x, cfg), batched=True)

        return jax.jit(run).lower(jax.ShapeDtypeStruct(stack_shape, dtype))


class YCHGEngine(Engine):
    """Deprecated alias for :class:`Engine` pinned to ``op="ychg"``.

    Kept so the PR 2 migration table stays valid; emits a
    ``DeprecationWarning`` exactly like ``core.api.analyze_image`` does.
    CI's warning-strict jobs keep in-repo callers off this shim.
    """

    def __init__(self, config: YCHGConfig = YCHGConfig(), *,
                 mesh: Optional[Mesh] = None):
        warnings.warn(
            "YCHGEngine is deprecated; use repro.engine.Engine "
            "(op defaults to 'ychg')",
            DeprecationWarning,
            stacklevel=2,
        )
        super().__init__(config, op="ychg", mesh=mesh)
