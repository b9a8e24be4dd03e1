"""Whole-scene cells: ``Engine.analyze(scene).to_host()`` on host scenes.

Set-up makes ``distinct_scenes`` seeded scenes on the device and brings
them to the host, where a user's scene is (``make_inputs``, which the
control reads too), and analyses each once, which
compiles the program. The window then analyses them in turn, each call
taking its scene from the host and its result back, until ``--seconds``
have passed; a call that started in the window is finished and counted.
End-to-end: ``scene_mpx_s``, scene megapixels whose result reached the host,
over the seconds until the last of them did. Every call's result is
compared with the NumPy reference of its scene.
"""

from __future__ import annotations

import time

import harness
import masks
import observe
import reference


def make_inputs(traffic: dict, seed: int) -> list:
    """The cell's distinct scenes, (height, width) uint8 host arrays."""
    return [masks.device_scene(seed * 7919 + i, traffic["height"],
                               traffic["width"], traffic["coverage"],
                               traffic["octaves"])
            for i in range(traffic["distinct_scenes"])]


def run(ctx) -> harness.Outcome:
    from repro.engine import Engine, YCHGConfig

    cell = ctx.cell
    h, w = cell.traffic["height"], cell.traffic["width"]
    scenes = make_inputs(cell.traffic, ctx.seed)
    harness.log(f"bench: {len(scenes)} scenes made")
    engine = Engine(YCHGConfig(**cell.config.get("engine", {})))
    for s in scenes:
        engine.analyze(s).to_host()
    harness.log(f"bench: backend {engine.resolve_backend()}; "
                f"{len(scenes)} scenes of {h}x{w}; set-up compiles "
                f"{ctx.clock.count} in {ctx.clock.seconds:.1f}s")

    got = []
    with ctx.window() as win:
        setup_s = win.t0 - ctx.t_start
        t = win.t0
        while t - win.t0 < ctx.seconds:
            got.append(engine.analyze(scenes[len(got) % len(scenes)]).to_host())
            t = time.monotonic()
            win.marks.append(t)
        win.t1 = t
    harness.log(f"bench: {len(got)} scenes in {win.t1 - win.t0:.3f}s")

    wants = [reference.analyze(s) for s in scenes]
    wrong = sum(not reference.same(g, wants[i % len(wants)])
                for i, g in enumerate(got))
    return harness.Outcome(
        metrics={"scene_mpx_s": len(got) * h * w / 1e6 / (win.t1 - win.t0),
                 "setup_s": setup_s},
        attempted=len(got), failed=wrong,
        checks={"bad_results": (wrong, 0)}, window=win,
        ychg_bytes=len(got) * observe.ychg_floor_bytes(h * w, 1, w))
