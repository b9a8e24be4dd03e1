#!/usr/bin/env python3
"""Prove that the yCHG platform's main path runs on TPU chips.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips of one host

One chip, three phases, all in this one process:

  service  ``Service`` + ``ServerThread`` on loopback with the default
           ladder (128/256/512/1024, max_batch=8); ``YCHGClient`` requests
           for every op (ychg, ccl, denoise) at every ladder side, plus one
           denoise->ychg ``/v1/pipeline`` request.
  bulk     ``serve.py scene``'s resumable bulk job (``BulkJob``) on one
           synthetic MODIS L1B 250 m granule: 8120 rows x 5416 columns.
  scene    one whole-scene ``Engine.analyze`` of the paper's 21000^2 scene,
           which takes the H-streamed fused kernel.

Four chips, two legs and nothing else:

  fleet    ``FleetRouter`` over four workers, each pinned to its own chip,
           checked against ``core.serial.analyze_numpy`` (NumPy: this
           process does not touch the TPU while the workers hold it);
  mesh     after the workers are gone, one process holds all four chips:
           a granule stack through ``Engine(mesh=make_batch_mesh())``,
           spread over the four devices and bit-identical to the unmeshed
           engine.

Every result is compared exactly (values, dtypes, shapes) with its
reference: on one chip, the jnp reference (``backend="jax"``) on the same
chip. Data is generated from ``--seed``. Earlier lines name each phase's
resolved backends (never ``jax``), its compile seconds, the compile-cache
directory and the device kind. The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure, or a host without a TPU, exits nonzero without it.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

OPS = ("ychg", "ccl", "denoise")
LADDER = (128, 256, 512, 1024)
GRANULE_HW = (8120, 5416)     # MODIS L1B 250 m: 2030 scans x 4, 1354 x 4
SCENE_SIDE = 21000            # the paper's largest scene


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def same(got: dict, want: dict, what: str) -> None:
    """Exact equality of two host result dicts, dtypes and shapes included."""
    if set(got) != set(want):
        fail(f"{what}: fields {sorted(got)} != reference {sorted(want)}")
    for field, w in want.items():
        a, b = np.asarray(got[field]), np.asarray(w)
        if a.dtype != b.dtype or a.shape != b.shape or not np.array_equal(a, b):
            fail(f"{what}: field {field!r} differs from the reference "
                 f"({a.dtype}{a.shape} vs {b.dtype}{b.shape})")


class CompileClock:
    """Seconds and count of XLA backend compiles in this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def since(self, mark):
        return f"compile {self.seconds - mark[0]:.1f}s ({self.count - mark[1]} compiles)"

    def mark(self):
        return self.seconds, self.count


def tpu_device(count: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        fail(f"JAX gives {len(devs)} {devs[0].platform} device(s); "
             f"needs {count} TPU chip(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_backends(engine, ops) -> dict:
    got = {op: engine.resolve_backend(op) for op in ops}
    if any(b not in ("fused", "pallas") for b in got.values()):
        fail(f"auto resolved to a non-kernel backend: {got}")
    return got


def phase_service(ref, clock, seed: int) -> None:
    from repro.data import modis
    from repro.engine import Engine
    from repro.frontend import ServerThread, YCHGClient
    from repro.service import Service, ServiceConfig

    cfg = ServiceConfig()
    if cfg.bucket_sides != LADDER or cfg.max_batch != 8:
        fail(f"default ladder is {cfg.bucket_sides} x {cfg.max_batch}")
    masks = []
    for i, side in enumerate(LADDER):
        for j in range(2):
            m = modis.snowfield(side, seed=seed + 10 * i + j)
            masks += [m, m[: side - side // 8, : side - side // 16]]
    reqs = [(op, m) for op in OPS for m in masks]
    mark, t0 = clock.mark(), time.perf_counter()
    with Service(Engine(), cfg) as svc, ServerThread(svc) as srv:
        backends = check_backends(svc.engine, OPS)

        def one(req):
            op, m = req
            with YCHGClient("127.0.0.1", srv.port) as c:
                return c.analyze(m, op=op)

        with ThreadPoolExecutor(8) as pool:
            got = list(pool.map(one, reqs))
        with YCHGClient("127.0.0.1", srv.port) as c:
            piped = c.pipeline(masks[-1], ["denoise", "ychg"])
    wall = time.perf_counter() - t0
    for (op, m), g in zip(reqs, got):
        same(g, ref.analyze(m, op=op).to_host(), f"service {op} {m.shape}")
    image = ref.analyze(masks[-1], op="denoise").to_host()["image"]
    same(piped, ref.analyze(image, op="ychg").to_host(),
         "service pipeline denoise+ychg")
    log(f"[service] ok: {len(reqs)} requests + 1 pipeline over loopback at "
        f"sides {LADDER}, max_batch {cfg.max_batch}; backends {backends}; "
        f"{clock.since(mark)}; wall {wall:.1f}s")


def phase_bulk(ref, clock, seed: int) -> None:
    from repro.engine import Engine
    from repro.launch import serve
    from repro.scene import GranuleReader, read_scene_result, synthetic_manifest

    h, w = GRANULE_HW
    backends = check_backends(Engine(), ("ychg",))
    mark, t0 = clock.mark(), time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        args = serve.build_parser().parse_args([
            "scene", "--granules", "1", "--scene-height", str(h),
            "--scene-width", str(w), "--seed", str(seed),
            "--out", f"{tmp}/out", "--ckpt", f"{tmp}/ckpt"])
        serve.scene_run(args)
        spec = synthetic_manifest(1, h, w, seed=seed)[0]
        got = read_scene_result(f"{tmp}/out/{spec.granule_id}.ychg")
    wall = time.perf_counter() - t0
    granule = GranuleReader.open(spec, h).read_tile(0)
    same(got.to_host(), ref.analyze(granule).to_host(), "bulk granule")
    log(f"[bulk] ok: {h}x{w} granule in {got.n_tiles} strips of "
        f"{got.tile_h} rows; backends {backends}; {clock.since(mark)}; "
        f"wall {wall:.1f}s")


def phase_scene(ref, clock, seed: int) -> None:
    from repro.data import scenes
    from repro.engine import Engine
    from repro.kernels import ops as kops

    n = SCENE_SIDE
    scene = np.empty((n, n), np.uint8)
    for r in range(0, n, 1000):
        scene[r:r + 1000] = scenes.scene_rows(n, n, r, min(r + 1000, n),
                                              seed=seed)
    engine = Engine()
    backends = check_backends(engine, ("ychg",))
    cfg = engine.config
    if not kops.uses_streamed(n, block_w=cfg.block_w,
                              vmem_budget=cfg.stream_vmem_budget):
        fail(f"a {n}^2 scene would not take the streamed fused kernel")
    mark, t0 = clock.mark(), time.perf_counter()
    got = engine.analyze(scene).to_host()
    wall = time.perf_counter() - t0
    want = ref.analyze(scene).to_host()
    same(got, want, f"scene {n}^2")
    log(f"[scene] ok: {n}x{n} through the streamed fused kernel, "
        f"{int(got['n_hyperedges'])} hyperedges; backends {backends}; "
        f"{clock.since(mark)}; wall {wall:.1f}s")


def leg_fleet(seed: int) -> None:
    from jax._src import xla_bridge

    from repro.core import serial
    from repro.data import modis
    from repro.fleet import (FleetRouter, FleetSupervisor, HashRing,
                             RouterConfig, RouterThread)
    from repro.fleet.router import routing_key
    from repro.frontend import YCHGClient

    masks = [modis.snowfield(side, seed=seed + 10 * i + j)
             for i, side in enumerate(LADDER) for j in range(8)]
    want = [serial.analyze_numpy(m) for m in masks]
    ladder = ",".join(map(str, LADDER))
    t0 = time.perf_counter()
    sup = FleetSupervisor(4, worker_args=["--buckets", ladder,
                                          "--max-batch", "8"])
    try:
        links = sup.start()
        devices = [l.device for l in links]
        if devices != [f"tpux1:chip{i}" for i in range(4)]:
            fail(f"workers are not pinned one chip each: {devices}")
        router = FleetRouter(links, RouterConfig(bucket_sides=LADDER,
                                                 max_batch=8),
                             supervisor=sup)
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            client.wait_ready(timeout=600)
            items = {it.id: it for it in client.analyze_batch(masks)}
        owners = {HashRing([l.name for l in links]).node_for(routing_key(m))
                  for m in masks}
    finally:
        sup.stop()
    for i, w in enumerate(want):
        if i not in items or not items[i].ok:
            fail(f"fleet: mask {i} failed: {items.get(i)}")
        same(items[i].result, w, f"fleet mask {i}")
    if len(owners) != 4:
        fail(f"fleet: masks landed on {sorted(owners)} only")
    if xla_bridge.backends_are_initialized():
        fail("fleet: this process started a JAX backend while the workers "
             "held the chips")
    log(f"[fleet] ok: {len(masks)} masks through the router over 4 workers "
        f"on chips {devices}, every worker owning some, bit-identical to "
        f"the NumPy reference; wall {time.perf_counter() - t0:.1f}s")


def leg_mesh(clock, seed: int) -> dict:
    from jax.sharding import PartitionSpec as P

    from repro.engine import Engine
    from repro.scene import GranuleReader, synthetic_manifest
    from repro.sharding.ychg import make_batch_mesh

    device = tpu_device(4)
    h, w = GRANULE_HW
    spec = synthetic_manifest(1, h, w, seed=seed)[0]
    stack = GranuleReader.open(spec, h // 8).read_stack(0, 8)
    mesh = make_batch_mesh()
    meshed_engine = Engine(mesh=mesh)
    backends = check_backends(meshed_engine, ("ychg",))
    mark = clock.mark()
    meshed = meshed_engine.analyze_batch(stack)
    shards = meshed.runs.addressable_shards
    if (meshed.runs.sharding.spec != P("data")
            or len({s.device for s in shards}) != 4
            or any(s.data.shape != (2, w) for s in shards)):
        fail(f"mesh: the stack's results are not spread over 4 devices: "
             f"{meshed.runs.sharding}")
    same(meshed.to_host(), Engine().analyze_batch(stack).to_host(),
         "mesh vs unmeshed")
    log(f"[mesh] ok: {stack.shape} granule stack over a 4-device batch "
        f"mesh, 2 strips per chip, bit-identical to the unmeshed engine; "
        f"backends {backends}; {clock.since(mark)}")
    return device


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip legs: fleet and mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"no repro package under {src}: run this from a checkout")
    sys.path.insert(0, str(src))
    from repro.fleet.chips import host_tpu_chips
    from repro.launch.compilecache import enable_compile_cache

    need = 4 if args.four_chips else 1
    chips = host_tpu_chips()
    if chips < need:
        fail(f"needs {need} TPU chip(s); this host has {chips}")
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.four_chips:
        leg_fleet(args.seed)       # before this process touches the TPU
        device = leg_mesh(clock, args.seed)
    else:
        from repro.engine import Engine, YCHGConfig

        device = tpu_device(1)
        log(f"device: {device['kind']} x{device['count']}")
        ref = Engine(YCHGConfig(backend="jax"))
        phase_service(ref, clock, args.seed)
        phase_bulk(ref, clock, args.seed)
        phase_scene(ref, clock, args.seed)
    log(f"all phases passed; compile {clock.seconds:.1f}s over "
        f"{clock.count} compiles")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
