"""Tile cells: ``Engine.analyze(tile, op="ccl").to_host()`` on host tiles.

Set-up makes ``distinct_tiles`` seeded water tiles on the device
(``bench/water.py``; ``make_inputs``) and brings them to the host, where a
user's tiles are, then labels each once, which compiles the program. That
is ``setup_s``. The NumPy reference of each tile is computed next, once,
outside both the set-up and the window. The window is a closed loop, one
call at a time, over the tiles in turn, until ``--seconds`` have passed;
a call that started in the window is finished and counted. After each
call its labels and ``n_components`` are compared exactly with its tile's
reference and dropped, so no more than one answer is held at a time.
End-to-end: ``mpx_s``, megapixels of the calls whose labels reached the
host over the sum of those calls' own seconds (from ``analyze`` to the
end of ``to_host``); the comparisons are not timed. ``coverage`` in the
traffic file is the water share the tiles come out with.
"""

from __future__ import annotations

import time

import harness
import reference_ccl
import water


def make_inputs(traffic: dict, seed: int) -> list:
    """The cell's distinct tiles, (height, width) uint8 host arrays."""
    return [water.tile(seed * 7919 + i, traffic)
            for i in range(traffic["distinct_tiles"])]


def run(ctx) -> harness.Outcome:
    from repro.engine import Engine, YCHGConfig

    cell = ctx.cell
    h, w = cell.traffic["height"], cell.traffic["width"]
    tiles = make_inputs(cell.traffic, ctx.seed)
    harness.log(f"bench: {len(tiles)} tiles made, water share "
                f"{sum(t.mean() for t in tiles) / len(tiles):.4f}")
    engine = Engine(YCHGConfig(**cell.config.get("engine", {})))
    for t in tiles:
        engine.analyze(t, op="ccl").to_host()
    setup_s = time.monotonic() - ctx.t_start
    harness.log(f"bench: backend {engine.resolve_backend('ccl')}; "
                f"{len(tiles)} tiles of {h}x{w}; set-up compiles "
                f"{ctx.clock.count} in {ctx.clock.seconds:.1f}s")
    wants = [reference_ccl.labels(t) for t in tiles]
    harness.log(f"bench: reference: "
                f"{[int(x['n_components']) for x in wants]} components")

    calls = wrong = 0
    busy = 0.0
    with ctx.window() as win:
        t = win.t0
        while t - win.t0 < ctx.seconds:
            i = calls % len(tiles)
            a = time.monotonic()
            got = engine.analyze(tiles[i], op="ccl").to_host()
            b = time.monotonic()
            busy += b - a
            calls += 1
            win.marks.append(b)
            wrong += not reference_ccl.same(got, wants[i])
            del got
            t = time.monotonic()
        win.t1 = t
    harness.log(f"bench: {calls} calls, {busy:.3f}s of them in "
                f"{win.t1 - win.t0:.3f}s; {wrong} wrong")
    return harness.Outcome(
        metrics={"mpx_s": calls * h * w / 1e6 / busy, "setup_s": setup_s},
        attempted=calls, failed=wrong,
        checks={"bad_results": (wrong, 0)}, window=win)
