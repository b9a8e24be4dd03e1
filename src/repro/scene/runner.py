"""SceneRunner — tile-stack streaming analysis with exact seam stitching.

Why tiling is exact here (the stitch invariant the tests pin): yCHG step 1
is a per-column count of rising edges down the scene. Split the scene into
full-width strips and count each strip independently, and every run that
*crosses* a strip boundary is counted twice — once by the strip that ends
it and once by the strip that starts it, because the lower strip sees its
first row with no predecessor. The overcount at each seam is exactly

    seam[j] = (bottom row of upper strip)[j] nonzero
              AND (top row of lower strip)[j] nonzero

so ``scene_runs = sum(strip_runs) - sum(seams)`` reproduces the
whole-scene count **bit for bit** (pure int32 arithmetic, no tolerance).
This is the streamed Pallas kernel's carry-row recurrence lifted from
VMEM tiles to host-scale strips; step 2 (births/deaths/transitions) is
then computed once from the stitched run vector with the same
``core.ychg`` formulas the engine backends are held bit-identical to, so
the full seven-field result equals a single whole-scene ``engine.analyze``
call — dtypes included.

The runner streams (stack_tiles, tile_h, W) stacks through
``engine.analyze_stream`` (strip ingest overlaps device compute); when the
engine carries a mesh, each stack is shard_mapped across its devices —
``Engine._run_meshed`` already pads ragged stacks, so the runner does
not care. Inside each strip, tall tiles past the VMEM budget take the
kernel's own streamed carry-row variant via the engine's existing
heuristic. State between stacks is three small host arrays
(:class:`SceneState`), which is what makes bulk jobs checkpointable: a
resumed job restores the state and continues from the next tile row.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Iterator, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import ychg
from repro.engine import Engine
from repro.obs import maybe_trace, use_trace
from repro.scene.granule import GranuleReader
from repro.scene.result import SceneResult

DEFAULT_STACK_TILES = 4


# --------------------------------------------------------------- progress


@dataclasses.dataclass(frozen=True)
class SceneProgressSnapshot:
    """Point-in-time view of a scene/bulk job (immutable)."""

    tiles_done: int = 0
    tiles_total: int = 0
    granules_done: int = 0
    granules_total: int = 0
    resumes: int = 0
    stitch_time_s: float = 0.0


class SceneProgress:
    """Thread-safe progress sink shared by runner, bulk job, and metrics.

    Attach to a :class:`repro.service.YCHGService` via
    ``service.attach_scene_progress(progress)`` and the counters surface
    in ``ServiceMetrics`` and on the frontend ``/metrics`` page.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._snap = SceneProgressSnapshot()

    def set_totals(self, *, tiles: int, granules: int) -> None:
        with self._lock:
            self._snap = dataclasses.replace(
                self._snap, tiles_total=tiles, granules_total=granules)

    def note_tiles(self, n: int) -> None:
        with self._lock:
            self._snap = dataclasses.replace(
                self._snap, tiles_done=self._snap.tiles_done + n)

    def note_granule_done(self) -> None:
        with self._lock:
            self._snap = dataclasses.replace(
                self._snap, granules_done=self._snap.granules_done + 1)

    def note_resume(self) -> None:
        with self._lock:
            self._snap = dataclasses.replace(
                self._snap, resumes=self._snap.resumes + 1)

    def note_stitch(self, dt_s: float) -> None:
        with self._lock:
            self._snap = dataclasses.replace(
                self._snap, stitch_time_s=self._snap.stitch_time_s + dt_s)

    def snapshot(self) -> SceneProgressSnapshot:
        with self._lock:
            return self._snap


# ------------------------------------------------------------------ state


@dataclasses.dataclass
class SceneState:
    """Resumable per-granule accumulator: everything a restart needs.

    ``runs`` is the seam-corrected per-column run count over tiles
    ``[0, next_tile)``; ``prev_bottom`` is the binarised last real row of
    the most recent strip (the carry row for the next seam). All three
    are plain host arrays, so the state round-trips through
    :class:`repro.checkpoint.Checkpointer` as a pytree.
    """

    next_tile: int
    runs: np.ndarray         # (W,) int32
    prev_bottom: np.ndarray  # (W,) uint8 (0/1)

    @classmethod
    def fresh(cls, width: int) -> "SceneState":
        return cls(next_tile=0, runs=np.zeros(width, np.int32),
                   prev_bottom=np.zeros(width, np.uint8))


def seam_joins(bottom_row: np.ndarray, top_row: np.ndarray) -> np.ndarray:
    """(W,) int32 count of runs continuing across one strip boundary."""
    return ((np.asarray(bottom_row) != 0)
            & (np.asarray(top_row) != 0)).astype(np.int32)


def stitch_tile_runs(tile_runs: Sequence[np.ndarray],
                     tiles: Sequence[np.ndarray]) -> np.ndarray:
    """Stitch per-strip run counts analysed *independently* (no carry).

    ``tile_runs[i]`` must be the (W,) step-1 output for strip ``tiles[i]``
    — e.g. per-tile results replayed through the HTTP front end — and the
    strips must be consecutive and overlap-free. Returns the whole-scene
    (W,) int32 run vector, bit-identical to analysing the unsplit scene.
    """
    if len(tile_runs) != len(tiles):
        raise ValueError(f"{len(tile_runs)} run vectors for "
                         f"{len(tiles)} tiles")
    total = np.zeros_like(np.asarray(tile_runs[0], np.int32))
    prev_bottom: Optional[np.ndarray] = None
    for runs, tile in zip(tile_runs, tiles):
        tile = np.asarray(tile)
        total += np.asarray(runs, np.int32)
        if prev_bottom is not None:
            total -= seam_joins(prev_bottom, tile[0])
        prev_bottom = tile[-1]
    return total


# ----------------------------------------------------------------- runner


class SceneRunner:
    """Streams one granule's tile stacks through an engine and stitches.

    The engine is used as-is: its backend policy, tile sizes, and optional
    mesh all apply per stack. ``stack_tiles`` strips batch into one
    ``(stack_tiles, tile_h, W)`` device computation.
    """

    def __init__(self, engine: Optional[Engine] = None, *,
                 stack_tiles: int = DEFAULT_STACK_TILES):
        if stack_tiles < 1:
            raise ValueError(f"stack_tiles must be >= 1, got {stack_tiles}")
        self.engine = engine if engine is not None else Engine()
        self.stack_tiles = stack_tiles

    # -- incremental API (what BulkJob drives) ------------------------------

    def update(self, state: SceneState, stack: np.ndarray,
               runs_b: np.ndarray) -> SceneState:
        """Fold one analysed stack into the accumulator (in place).

        ``stack`` is the (b, tile_h, W) host strips; ``runs_b`` the
        matching (b, W) step-1 output. Seam corrections use the strips'
        own boundary rows, so the math is exact whatever ``b`` was.
        """
        stack = np.asarray(stack)
        runs_b = np.asarray(runs_b)
        b = stack.shape[0]
        tops = stack[:, 0, :] != 0
        bottoms = stack[:, -1, :] != 0
        prevs = np.concatenate(
            [(state.prev_bottom != 0)[None], bottoms[:-1]], axis=0)
        seams = tops & prevs
        state.runs += (runs_b.sum(axis=0, dtype=np.int32)
                       - seams.sum(axis=0, dtype=np.int32))
        state.prev_bottom = bottoms[-1].astype(np.uint8)
        state.next_tile += b
        return state

    def finalize(self, reader: GranuleReader, state: SceneState,
                 progress: Optional[SceneProgress] = None) -> SceneResult:
        """Stitched runs -> the full seven-field scene result.

        Step 2 runs once over the stitched (W,) vector with the exact
        ``core.ychg`` formulas (dtypes included), so the output equals a
        single whole-scene ``engine.analyze`` call bit for bit.
        """
        if state.next_tile != reader.n_tiles:
            raise ValueError(
                f"granule {reader.granule_id!r}: finalize at tile "
                f"{state.next_tile} of {reader.n_tiles}")
        t0 = time.perf_counter()
        runs = jnp.asarray(state.runs)
        t = ychg.hyperedge_transitions(runs)
        result = SceneResult(
            granule_id=reader.granule_id,
            height=reader.height,
            width=reader.width,
            tile_h=reader.tile_h,
            n_tiles=reader.n_tiles,
            runs=np.asarray(runs),
            cut_vertices=np.asarray(2 * runs),
            transitions=np.asarray(t["transitions"]),
            births=np.asarray(t["births"]),
            deaths=np.asarray(t["deaths"]),
            n_hyperedges=np.asarray(jnp.sum(t["births"], axis=-1)),
            n_transitions=np.asarray(
                jnp.sum(t["transitions"], axis=-1, dtype=jnp.int32)),
        )
        if progress is not None:
            progress.note_stitch(time.perf_counter() - t0)
        return result

    # -- one-call streaming API ---------------------------------------------

    def analyze_scene(self, reader: GranuleReader, *,
                      progress: Optional[SceneProgress] = None,
                      state: Optional[SceneState] = None,
                      trace=None) -> SceneResult:
        """Stream the whole granule (from ``state`` if given) and stitch.

        Stacks flow through ``engine.analyze_stream``, so strip reading
        and host->device transfer of stack n+1 overlap the device compute
        of stack n — the service's double-buffering discipline applied to
        the offline path. When tracing is on, each stack leaves
        ``scene.read`` / ``scene.compute`` (stream wait, which overlaps
        the *next* read by design) / ``scene.stitch`` spans plus one
        ``scene.finalize`` span on the trace. ``scene.sync``, the wait for
        the stack's runs, nests in ``scene.stitch``; the engine's
        ``engine.put`` / ``engine.dispatch`` nest in ``scene.compute``.
        """
        tr = trace if trace is not None else maybe_trace(process="scene")
        own = trace is None
        state = state if state is not None else SceneState.fresh(reader.width)
        pending: "collections.deque[np.ndarray]" = collections.deque()

        def stacks() -> Iterator[np.ndarray]:
            t = state.next_tile
            while t < reader.n_tiles:
                n = min(self.stack_tiles, reader.n_tiles - t)
                with tr.span("scene.read", granule=reader.granule_id,
                             tile=t, tiles=n):
                    s = reader.read_stack(t, n)
                pending.append(s)
                yield s
                t += n

        try:
            with use_trace(tr):
                t_wait = time.monotonic()
                for res in self.engine.analyze_stream(stacks()):
                    t_got = time.monotonic()
                    stack = pending.popleft()
                    # the wait spans the stream's resumptions, so it is
                    # recorded from its edges (no profiler annotation)
                    tr.add("scene.compute", t_wait, t_got,
                           granule=reader.granule_id, tiles=stack.shape[0])
                    with tr.span("scene.stitch",
                                 granule=reader.granule_id) as sp:
                        s0 = time.monotonic()
                        with tr.span("scene.sync", bytes=res.runs.nbytes):
                            runs = np.asarray(res.runs)
                        self.update(state, stack, runs)
                        s1 = time.monotonic()
                        sp.stamp(s0, s1)
                    if progress is not None:
                        progress.note_stitch(s1 - s0)
                        progress.note_tiles(stack.shape[0])
                    t_wait = time.monotonic()
            with tr.span("scene.finalize", granule=reader.granule_id):
                return self.finalize(reader, state, progress)
        finally:
            if own:
                tr.finish()
