"""CLI serve driver: the paper's image-analysis service on mask batches
(the default ``--workload ychg``), in-process or over the network front
end; ``--workload lm --arch <id> --smoke`` serves a language model.

Every mode first turns on the persistent compilation cache
(``launch.compilecache``: ``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --res 2048
  PYTHONPATH=src python -m repro.launch.serve --workload lm \\
      --arch qwen2-0.5b --smoke
  # network modes (repro.frontend):
  PYTHONPATH=src python -m repro.launch.serve \\
      --listen 127.0.0.1:8788                  # serve over loopback HTTP
  PYTHONPATH=src python -m repro.launch.serve \\
      --connect http://127.0.0.1:8788          # drive a remote server
  PYTHONPATH=src python -m repro.launch.serve \\
      --res 64 --batch 4 --frontend-smoke      # CI end-to-end assert
  PYTHONPATH=src python -m repro.launch.serve \\
      --fleet 4 --listen 127.0.0.1:8788        # router over 4 workers
  PYTHONPATH=src python -m repro.launch.serve \\
      --res 64 --batch 4 --fleet-smoke         # CI fleet assert
  # granule-scale bulk analysis (repro.scene):
  PYTHONPATH=src python -m repro.launch.serve scene \\
      --granules 4 --scene-height 4096 --scene-width 2048 \\
      --out results/ --ckpt ckpt/               # resumable bulk job
  PYTHONPATH=src python -m repro.launch.serve \\
      --scene-smoke                             # CI scene assert
  PYTHONPATH=src python -m repro.launch.serve \\
      --res 64 --batch 4 --slo-smoke            # CI traffic-class assert
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from repro import obs
from repro.launch.compilecache import enable_compile_cache


def serve_lm(args):
    import jax

    from repro.configs import get_config
    from repro.configs.archs import smoke_config
    from repro.models import count_params, init_params
    from repro.serve import ServeEngine

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"{cfg.name}: {count_params(cfg) / 1e6:.2f}M params")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=args.prompt + args.max_new)
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt)
    ).astype(np.int32)
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new=args.max_new, temperature=0.7)
    dt = time.perf_counter() - t0
    print(f"generated {out.tokens.size} tokens in {dt:.2f}s "
          f"({out.tokens.size / dt:.1f} tok/s)")


# one human-readable scalar per op for the workload's per-tile report
_OP_STAT_NAME = {"ychg": "hyperedges", "ccl": "components",
                 "denoise": "mean"}


def _op_stat(op, out):
    if op == "ychg":
        return int(np.asarray(out.n_hyperedges)[0])
    if op == "ccl":
        return int(np.asarray(out.n_components).reshape(-1)[0])
    return round(float(np.asarray(out.image).mean()), 4)


def serve_ychg(args):
    """The paper's image-analysis workload behind the production service:
    requests batch through YCHGService -> Engine (not the legacy
    core.ychg.analyze_jit call). Three timed passes separate the costs:
    cold (includes backend compile), warm (steady-state compute on fresh
    masks), cached (repeat traffic served from the result cache). With
    --overload, a fourth pass offers a burst to a bounded-queue service
    (overload_policy="shed") and reports the shed rate — the admission
    control path CI smoke-checks."""
    from repro.data import modis
    from repro.engine import Engine
    from repro.service import ServiceConfig, ServiceOverloaded, YCHGService

    op = args.op

    def timed_pass(svc, masks):
        t0 = time.perf_counter()
        outs = [f.result(timeout=600)
                for f in [svc.submit(m, op=op) for m in masks]]
        return time.perf_counter() - t0, outs

    masks = [modis.snowfield(args.res, seed=s) for s in range(args.batch)]
    fresh = [modis.snowfield(args.res, seed=args.batch + s)
             for s in range(args.batch)]
    px = args.batch * args.res * args.res
    engine = Engine()
    cfg = ServiceConfig(bucket_sides=(args.res,), max_batch=args.batch)
    with YCHGService(engine, cfg) as svc:
        t_cold, outs = timed_pass(svc, masks)       # compiles the bucket shape
        t_warm, _ = timed_pass(svc, fresh)          # steady-state compute
        before_cached = svc.metrics()
        t_cached, _ = timed_pass(svc, masks)        # repeat traffic: cache
        m = svc.metrics()
    # the cached pass's own hit rate (lifetime m.hit_rate would dilute it
    # with the cold/warm passes' unavoidable misses)
    cached_hit_rate = (m.cache_hits - before_cached.cache_hits) / args.batch
    edges = [_op_stat(op, o) for o in outs]
    print(f"{op} service[{m.backend}]: {args.batch} x {args.res}^2 masks")
    print(f"  cold  {t_cold * 1e3:8.1f}ms (includes compile)")
    print(f"  warm  {t_warm * 1e3:8.1f}ms ({px / t_warm / 1e6:.0f} Mpx/s)")
    print(f"  cached{t_cached * 1e3:8.1f}ms "
          f"({px / t_cached / 1e6:.0f} Mpx/s, hit rate {cached_hit_rate:.0%})")
    print(f"  p50 {m.p50_latency_ms:.1f}ms p95 {m.p95_latency_ms:.1f}ms over "
          f"{m.completed} requests ({m.completed_from_cache} from cache) "
          f"in {m.batches} device batches; {_OP_STAT_NAME[op]} per tile: "
          f"{edges}")
    if args.overload:
        # admission control under a deliberate burst: a bounded queue with
        # overload_policy="shed" fails the excess fast instead of letting
        # latency balloon. The long delay window holds the two admitted
        # requests pending, so the shed count is deterministic.
        n_burst = 4 * args.batch
        burst = [modis.snowfield(args.res, seed=10_000 + s)
                 for s in range(n_burst)]
        ocfg = ServiceConfig(bucket_sides=(args.res,), max_batch=args.batch,
                             max_delay_ms=200.0, max_queue_depth=2,
                             overload_policy="shed")
        shed, futures = 0, []
        with YCHGService(engine, ocfg) as osvc:
            for b in burst:
                try:
                    futures.append(osvc.submit(b))
                except ServiceOverloaded:
                    shed += 1
            om = osvc.metrics()
        for f in futures:
            f.result(timeout=600)   # admitted requests still resolve
        print(f"  overload burst of {n_burst} at max_queue_depth=2: "
              f"{len(futures)} admitted, {shed} shed "
              f"(shed rate {shed / n_burst:.0%})")
        if shed == 0 or om.shed != shed:
            raise SystemExit(
                "overload pass failed: admission control shed nothing")


def _parse_hostport(s: str, default_host: str = "127.0.0.1"):
    """"HOST:PORT", ":PORT", or "PORT" -> (host, port)."""
    if "//" in s:
        s = s.split("//", 1)[1]
    s = s.rstrip("/")
    host, _, port = s.rpartition(":")
    return (host or default_host), int(port)


def _service_config(args, **overrides):
    from repro.service import ServiceConfig

    sides = (tuple(int(b) for b in args.buckets.split(","))
             if args.buckets else (args.res,))
    knobs = dict(bucket_sides=sides, max_batch=args.batch,
                 max_queue_depth=args.max_queue_depth,
                 bucket_queue_depth=args.bucket_queue_depth,
                 overload_policy=args.policy)
    knobs.update(overrides)
    return ServiceConfig(**knobs)


def serve_listen(args):
    """Serve the ROI service over loopback/network HTTP (+ optional RPC)
    until interrupted — the production front end behind a CLI flag."""
    from repro.engine import Engine
    from repro.frontend import ServerThread
    from repro.service import YCHGService

    host, port = _parse_hostport(args.listen)
    rpc_port = (_parse_hostport(args.rpc_listen)[1]
                if args.rpc_listen else None)
    with YCHGService(Engine(), _service_config(args)) as svc:
        with ServerThread(svc, host=host, port=port,
                          rpc_port=rpc_port) as srv:
            extra = (f" (rpc on {host}:{srv.rpc_port})"
                     if rpc_port is not None else "")
            print(f"yCHG frontend listening on http://{host}:{srv.port}"
                  f"{extra}; buckets {svc.config.bucket_sides}, "
                  f"max_batch {svc.config.max_batch}", flush=True)
            try:
                threading.Event().wait()
            except KeyboardInterrupt:
                print("shutting down", flush=True)
                path = obs.auto_dump("serve-shutdown")
                if path:
                    print(f"flight recorder dumped to {path}", flush=True)


def serve_connect(args):
    """Client mode: drive a running front end with the mask workload and
    report wire-level timing (the network twin of the in-process pass)."""
    from repro.data import modis
    from repro.frontend import YCHGClient

    host, port = _parse_hostport(args.connect)
    masks = [modis.snowfield(args.res, seed=s) for s in range(args.batch)]
    px = args.batch * args.res * args.res
    with YCHGClient(host, port) as client:
        health = client.wait_ready(timeout=60.0)
        print(f"connected to {host}:{port}: backend {health['backend']}")
        t0 = time.perf_counter()
        items = list(client.analyze_batch(masks))
        dt = time.perf_counter() - t0
        failed = [it for it in items if not it.ok]
        if failed:
            raise SystemExit(
                f"{len(failed)} of {len(items)} requests failed; first: "
                f"{failed[0].status} {failed[0].error}")
        edges = [int(it.result["n_hyperedges"]) for it in
                 sorted(items, key=lambda it: it.id)]
        print(f"  wire  {dt * 1e3:8.1f}ms for {args.batch} x {args.res}^2 "
              f"masks ({px / dt / 1e6:.0f} Mpx/s); hyperedges: {edges}")


def frontend_smoke(args):
    """CI end-to-end assert over a real loopback socket (ephemeral port):

      1. a streamed client batch is BIT-IDENTICAL (values, dtypes, shapes)
         to in-process ``YCHGService.submit`` on the same masks;
      2. one traced request leaves a single flight-recorder trace whose
         spans cover client -> frontend -> scheduler -> engine in order
         (skipped under ``YCHG_TRACE=0``);
      3. every ``/metrics`` series parses as Prometheus text, histogram
         ``_sum``/``_count`` agree with their buckets, and the latency
         histogram's total count equals completed-minus-cache-served;
      4. at a full admission queue the wire answer is HTTP 429 with a
         Retry-After, and the service's shed counter moves (visible in
         /metrics down to the per-bucket counter).

    Exits nonzero on any failure — the frontend-smoke CI job runs this.
    """
    from repro.data import modis
    from repro.engine import Engine
    from repro.frontend import FrontendOverloaded, ServerThread, YCHGClient
    from repro.obs import base_family, parse_prom_text
    from repro.service import YCHGService

    masks = [modis.snowfield(args.res, seed=s) for s in range(args.batch)]
    engine = Engine()
    with YCHGService(engine, _service_config(args)) as svc, \
            ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        items = {it.id: it for it in client.analyze_batch(masks)}
        want = [svc.submit(m).result(timeout=600).to_host() for m in masks]
        for i, host_res in enumerate(want):
            item = items.get(i)
            if item is None or not item.ok:
                raise SystemExit(f"frontend smoke: mask {i} failed over the "
                                 f"wire: {item and item.error}")
            for field, arr in host_res.items():
                a, b = np.asarray(arr), item.result[field]
                if not (np.array_equal(a, b) and a.dtype == b.dtype
                        and a.shape == b.shape):
                    raise SystemExit(
                        f"frontend smoke: field {field!r} of mask {i} is "
                        f"not bit-identical over the wire")
        print(f"frontend smoke: {len(masks)} masks round-tripped over "
              f"loopback HTTP bit-identical to in-process submit")

        # trace leg: one fresh mask end to end, then one trace id in the
        # flight recorder must cover every stage of the request
        if obs.tracing_enabled():
            tid = obs.new_trace_id()
            client.analyze(modis.snowfield(args.res, seed=4242),
                           trace_id=tid)
            events = [e for e in client.debug_traces().get("traceEvents", [])
                      if e.get("args", {}).get("trace_id") == tid]
            names = {e["name"] for e in events}
            needed = {"client.encode", "client.wire", "frontend.parse",
                      "cache.probe", "scheduler.admission",
                      "scheduler.queue_wait", "scheduler.flush",
                      "engine.compute", "engine.crop"}
            if needed - names:
                raise SystemExit(f"frontend smoke [trace]: spans missing "
                                 f"from the flight recorder: "
                                 f"{sorted(needed - names)}")
            ts = {e["name"]: e["ts"] for e in events}
            chain = ["client.encode", "frontend.parse",
                     "scheduler.admission", "engine.compute", "engine.crop"]
            for a, b in zip(chain, chain[1:]):
                if ts[b] < ts[a]:   # same process, same clock: strict
                    raise SystemExit(f"frontend smoke [trace]: span {b!r} "
                                     f"starts before {a!r}")
            print("frontend smoke: one trace covers client -> frontend -> "
                  "scheduler -> engine with ordered spans", flush=True)

        # metrics leg: the whole page must parse; histograms must be
        # internally consistent and tie out against the request counters
        page = parse_prom_text(client.metrics_text())
        lat_count = 0.0
        for fam in sorted(n for n, t in page.types.items()
                          if t == "histogram"):
            series = {}
            for s in page.samples:
                if base_family(s.name) != fam:
                    continue
                key = tuple(p for p in s.labels if p[0] != "le")
                d = series.setdefault(key, {"b": [], "sum": None,
                                            "count": None})
                if s.name.endswith("_bucket"):
                    d["b"].append(s.value)
                elif s.name.endswith("_sum"):
                    d["sum"] = s.value
                elif s.name.endswith("_count"):
                    d["count"] = s.value
            for key, d in series.items():
                if d["sum"] is None or d["count"] is None or not d["b"]:
                    raise SystemExit(
                        f"frontend smoke [metrics]: histogram {fam} series "
                        f"{dict(key)} is missing _sum/_count/buckets")
                if d["b"] != sorted(d["b"]) or d["b"][-1] != d["count"]:
                    raise SystemExit(
                        f"frontend smoke [metrics]: histogram {fam} series "
                        f"{dict(key)} buckets disagree with _count")
                if fam == "ychg_request_latency_seconds":
                    lat_count += d["count"]

        def scalar(name):
            vals = [s.value for s in page.samples
                    if s.name == name and not s.labels]
            return vals[0] if vals else 0.0

        want_count = (scalar("ychg_completed_total")
                      - scalar("ychg_completed_from_cache_total"))
        if lat_count != want_count:
            raise SystemExit(
                f"frontend smoke [metrics]: latency histogram count "
                f"{lat_count} != completed-minus-cached {want_count}")
        print(f"frontend smoke: /metrics parsed clean; latency histogram "
              f"count {lat_count:.0f} ties out against the request "
              f"counters", flush=True)

    # overload leg: ONE admission slot, held by an in-process submit parked
    # in a long delay window, so the wire request deterministically sheds
    ocfg = _service_config(args, max_delay_ms=10_000.0, max_queue_depth=1,
                           bucket_queue_depth=1, overload_policy="shed")
    with YCHGService(engine, ocfg) as osvc:
        holder = osvc.submit(masks[0])
        with ServerThread(osvc) as srv, \
                YCHGClient("127.0.0.1", srv.port) as client:
            try:
                client.analyze(masks[1])
                raise SystemExit("frontend smoke: expected HTTP 429, "
                                 "got a result")
            except FrontendOverloaded as e:
                if not e.retry_after_s > 0:
                    raise SystemExit("frontend smoke: 429 carried no "
                                     "positive retry_after_s")
            metrics = client.metrics_text()
        for needle in ("ychg_shed_total 1", "ychg_shed_bucket_total{"):
            if needle not in metrics:
                raise SystemExit(
                    f"frontend smoke: {needle!r} missing from /metrics "
                    f"after an overload shed")
    holder.result(timeout=600)   # the admitted request still resolves
    print("frontend smoke: overload answered 429 with Retry-After and the "
          "per-bucket shed counter moved")


def op_smoke(args):
    """CI end-to-end assert for the multi-op platform over loopback HTTP:

      1. **per-op bit-identity** — for every registered op, one request
         over ``POST /v1/{op}`` is BIT-IDENTICAL (values, dtypes, shapes)
         to the op's in-repo reference function on the same input;
      2. **pipeline == separate requests** — one ``POST /v1/pipeline``
         compound request (denoise -> ychg, device-resident between
         stages) equals feeding stage 1's wire output back as stage 2's
         request, field for field;
      3. **routing** — an unknown op answers 404 JSON naming the
         registered ops, and ``/metrics`` exports the dispatch histogram
         with one ``op=`` label per op served.

    Exits nonzero on any failure — the op-smoke CI job runs this.
    """
    import json as _json

    import jax.numpy as jnp

    from repro.data import modis
    from repro.engine import Engine
    from repro.engine.ops import get_op, op_names
    from repro.frontend import FrontendError, ServerThread, YCHGClient
    from repro.service import ServiceConfig, YCHGService

    rng = np.random.default_rng(11)
    inputs = {
        "ychg": modis.snowfield(args.res, seed=0),
        "ccl": modis.snowfield(args.res, seed=1),
        "denoise": rng.random((args.res, args.res)).astype(np.float32),
    }
    cfg = ServiceConfig(bucket_sides=(args.res,), max_batch=args.batch)
    with YCHGService(Engine(), cfg) as svc, \
            ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        client.wait_ready(timeout=120.0)
        for op in sorted(op_names()):
            x = inputs[op]
            got = client.analyze(x, op=op)
            spec = get_op(op)
            # masks fill their bucket exactly (res == bucket side), so the
            # service's crop is the identity and the wire result must equal
            # the reference, rendered in the single-request (batched=False)
            # layout the service serves
            want = spec.from_summary(
                spec.reference(jnp.asarray(x)[None]), False).to_host()
            for field, arr in want.items():
                a, b = np.asarray(arr), got[field]
                if not (np.array_equal(a, b) and a.dtype == b.dtype
                        and a.shape == b.shape):
                    raise SystemExit(
                        f"op smoke [{op}]: field {field!r} not bit-identical "
                        f"to the in-repo reference over the wire")
            print(f"op smoke: /v1/{op} bit-identical to its reference",
                  flush=True)

        # pipeline leg: the compound request vs its stages as separate
        # wire requests — the device-resident chain must be bit-exact
        img = inputs["denoise"]
        compound = client.pipeline(img, ["denoise", "ychg"])
        stage1 = client.analyze(img, op="denoise")
        stage2 = client.analyze(stage1["image"], op="ychg")
        for field, arr in stage2.items():
            a, b = np.asarray(arr), compound[field]
            if not (np.array_equal(a, b) and a.dtype == b.dtype
                    and a.shape == b.shape):
                raise SystemExit(
                    f"op smoke [pipeline]: field {field!r} of the compound "
                    f"denoise+ychg request differs from separate requests")
        print("op smoke: /v1/pipeline denoise+ychg == the stages issued as "
              "separate requests", flush=True)

        # routing leg: unknown op -> 404 JSON naming the registry
        try:
            client.analyze(inputs["ychg"], op="warp")
            raise SystemExit("op smoke: unknown op answered 200")
        except FrontendError as e:
            if e.status != 404:
                raise SystemExit(
                    f"op smoke: unknown op answered {e.status}, wanted 404")
            body = _json.loads(str(e))
            if sorted(body.get("ops", [])) != sorted(op_names()):
                raise SystemExit(
                    f"op smoke: 404 body named ops {body.get('ops')}, "
                    f"wanted {sorted(op_names())}")
        metrics = client.metrics_text()
        for op in op_names():
            needle = f'ychg_engine_dispatch_seconds_count{{op="{op}"'
            if needle not in metrics:
                raise SystemExit(
                    f"op smoke: dispatch histogram missing an op={op!r} "
                    f"series after serving it")
        print("op smoke: unknown op answered 404 naming the registry; "
              "dispatch histogram carries one op= label per op", flush=True)


def slo_smoke(args):
    """CI end-to-end assert for traffic classes over loopback HTTP
    (docs/traffic.md): the class/deadline/tenant headers must reach the
    scheduler and change admission, visibly in the wire answer and on
    ``/metrics``.

      1. **priority preemption** — against a parked batch-class backlog,
         an interactive-class wire request overtakes the backlog: its
         completion timestamp precedes the last batch completion and
         batch requests are still pending when it returns. A
         deterministic sub-leg (one admission slot, held) then sheds a
         batch-class wire request and asserts the 429 carries
         ``kind="overload"`` and ``ychg_shed_class_total{class="batch"}``
         moves.
      2. **deadline shed** — with the drain-rate estimator white-box
         seeded to exactly 2 requests/s, a wire request with
         ``X-YCHG-Deadline-Ms: 100`` sheds at admission with
         ``kind="deadline"`` and the honest Retry-After
         ``predicted 0.5s - deadline 0.1s = 0.4s``; a dead-on-arrival
         ``deadline_ms=0`` probe sheds with the clamp floor (0.05s).
      3. **tenant quota** — a two-token burst tenant admits 2 of 4 wire
         requests and sheds the rest with ``kind="quota"`` and the
         30s-clamped Retry-After, while another tenant admits freely;
         ``ychg_shed_tenant_total{tenant="acme"}`` counts exactly the
         sheds.

    Exits nonzero on any failure — the slo-smoke CI job runs this.
    """
    from repro.data import modis
    from repro.engine import Engine
    from repro.frontend import FrontendOverloaded, ServerThread, YCHGClient
    from repro.service import ServiceConfig, YCHGService

    res, batch_res = args.res, 2 * args.res
    engine = Engine()

    def expect_shed(client, kind, **kw):
        try:
            client.analyze(modis.snowfield(res, seed=kw.pop("seed")), **kw)
        except FrontendOverloaded as e:
            if e.kind != kind:
                raise SystemExit(f"slo smoke: shed carried kind={e.kind!r}, "
                                 f"wanted {kind!r}")
            return e
        raise SystemExit(f"slo smoke: expected a {kind} 429, got a result")

    def counter(text, needle):
        for line in text.splitlines():
            if line.startswith(needle):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    # ---- leg 1: priority preemption against a live batch backlog
    cfg = ServiceConfig(bucket_sides=(res, batch_res),
                        max_batch=args.batch, max_delay_ms=2.0)
    with YCHGService(engine, cfg) as svc, \
            ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        client.wait_ready(timeout=120.0)
        # warm ONLY the interactive bucket: the batch backlog's first
        # flush then includes the batch bucket's compile, so the backlog
        # is reliably still pending when the interactive request lands
        client.analyze(modis.snowfield(res, seed=100), klass="interactive")
        done_at = {}
        batch_futs = [svc.submit(modis.snowfield(batch_res, seed=200 + i),
                                 klass="batch")
                      for i in range(4 * args.batch)]
        for i, f in enumerate(batch_futs):
            f.add_done_callback(
                lambda _f, i=i: done_at.setdefault(i, time.perf_counter()))
        client.analyze(modis.snowfield(res, seed=300), klass="interactive")
        t_interactive = time.perf_counter()
        pending = sum(1 for f in batch_futs if not f.done())
        for f in batch_futs:
            f.result(timeout=600)
        deadline = time.perf_counter() + 30.0
        while (len(done_at) < len(batch_futs)
               and time.perf_counter() < deadline):
            time.sleep(0.001)   # done-callbacks can lag result() briefly
        if pending == 0 or t_interactive >= max(done_at.values()):
            raise SystemExit(
                f"slo smoke [priority]: interactive request did not "
                f"overtake the batch backlog ({pending} of "
                f"{len(batch_futs)} batch requests pending at its "
                f"completion)")
        print(f"slo smoke: interactive wire request overtook the "
              f"batch-class backlog ({pending}/{len(batch_futs)} batch "
              f"requests still pending at its completion)", flush=True)

    # leg 1b: deterministic class-labelled shed — ONE admission slot,
    # held by a parked submit, so the batch-class wire request sheds
    ocfg = ServiceConfig(bucket_sides=(res,), max_batch=args.batch,
                         max_delay_ms=10_000.0, max_queue_depth=1,
                         bucket_queue_depth=1, overload_policy="shed")
    with YCHGService(engine, ocfg) as osvc:
        holder = osvc.submit(modis.snowfield(res, seed=400))
        with ServerThread(osvc) as srv, \
                YCHGClient("127.0.0.1", srv.port) as client:
            e = expect_shed(client, "overload", seed=401, klass="batch")
            if not e.retry_after_s > 0:
                raise SystemExit("slo smoke [priority]: overload 429 "
                                 "carried no positive retry_after_s")
            shed = counter(client.metrics_text(),
                           'ychg_shed_class_total{class="batch"}')
        holder.result(timeout=600)
    if shed != 1:
        raise SystemExit(f"slo smoke [priority]: shed_class_total for the "
                         f"batch class is {shed}, wanted 1")
    print('slo smoke: wire shed counted under '
          'ychg_shed_class_total{class="batch"}', flush=True)

    # ---- leg 2: deadline shed with an honest Retry-After. Seed the
    # drain-rate estimator white-box to exactly 2 req/s on an idle
    # service (depth 0): predicted wait is (0+1)/2 = 0.5s, so a 100ms
    # deadline sheds with retry_after = 0.5 - 0.1 = 0.4s exactly.
    with YCHGService(engine, ServiceConfig(
            bucket_sides=(res,), max_batch=args.batch)) as svc, \
            ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        # cold estimator first: deadline_ms=0 is dead on arrival even
        # without evidence, and its zero lateness clamps to the floor
        dead = expect_shed(client, "deadline", seed=501, deadline_ms=0.0)
        if abs(dead.retry_after_s - 0.05) > 1e-9:
            raise SystemExit(
                f"slo smoke [deadline]: dead-on-arrival retry_after_s "
                f"{dead.retry_after_s} != the 0.05s clamp floor")
        est = svc._scheduler._drain_rate
        est.observe(0, now=0.0)
        est.observe(20, now=10.0)
        e = expect_shed(client, "deadline", seed=500, deadline_ms=100.0)
        if abs(e.retry_after_s - 0.4) > 1e-9:
            raise SystemExit(
                f"slo smoke [deadline]: retry_after_s {e.retry_after_s} "
                f"!= the honest lateness 0.4s (predicted 0.5s - "
                f"deadline 0.1s)")
        sheds = counter(client.metrics_text(), "ychg_shed_deadline_total")
        if sheds != 2:
            raise SystemExit(f"slo smoke [deadline]: "
                             f"ychg_shed_deadline_total {sheds}, wanted 2")
    print("slo smoke: 100ms deadline shed at admission with the honest "
          "0.4s Retry-After; dead-on-arrival probe shed at the clamp "
          "floor", flush=True)

    # ---- leg 3: tenant token buckets over the wire. burst=2 at a
    # starvation refill rate: 2 of 4 "acme" requests admit, 2 shed with
    # the 30s-clamped Retry-After; "beta" admits freely.
    tcfg = ServiceConfig(bucket_sides=(res,), max_batch=args.batch,
                         max_delay_ms=2.0, tenant_rate=0.001,
                         tenant_burst=2)
    with YCHGService(engine, tcfg) as svc, \
            ServerThread(svc) as srv, \
            YCHGClient("127.0.0.1", srv.port) as client:
        admitted, sheds = 0, 0
        for i in range(4):
            try:
                client.analyze(modis.snowfield(res, seed=600 + i),
                               tenant="acme")
                admitted += 1
            except FrontendOverloaded as e:
                if e.kind != "quota":
                    raise SystemExit(f"slo smoke [quota]: shed carried "
                                     f"kind={e.kind!r}, wanted 'quota'")
                if e.retry_after_s != 30.0:
                    raise SystemExit(
                        f"slo smoke [quota]: retry_after_s "
                        f"{e.retry_after_s} != the 30s clamp for a "
                        f"starvation-rate refill")
                sheds += 1
        client.analyze(modis.snowfield(res, seed=700), tenant="beta")
        metrics = client.metrics_text()
        if (admitted, sheds) != (2, 2):
            raise SystemExit(f"slo smoke [quota]: burst 2 of 4 offered "
                             f"should admit 2 and shed 2, got "
                             f"({admitted}, {sheds})")
        by_tenant = counter(metrics, 'ychg_shed_tenant_total{tenant="acme"}')
        if by_tenant != sheds or counter(
                metrics, "ychg_shed_quota_total") != sheds:
            raise SystemExit(
                f"slo smoke [quota]: /metrics counted {by_tenant} acme "
                f"sheds, client saw {sheds}")
    print("slo smoke: tenant quota admitted the burst, shed the rest "
          "with kind=quota and the clamped Retry-After; counters tie "
          "out per tenant", flush=True)


def _worker_args(args):
    """Worker-CLI knobs mirroring this invocation's service knobs."""
    wa = ["--buckets", args.buckets if args.buckets else str(args.res),
          "--max-batch", str(args.batch), "--policy", args.policy]
    if args.max_queue_depth is not None:
        wa += ["--max-queue-depth", str(args.max_queue_depth)]
    if args.bucket_queue_depth is not None:
        wa += ["--bucket-queue-depth", str(args.bucket_queue_depth)]
    if args.trace_dump:
        wa += ["--trace-dump", args.trace_dump]
    return wa


def _router_config(args, **overrides):
    from repro.fleet import RouterConfig

    sides = (tuple(int(b) for b in args.buckets.split(","))
             if args.buckets else (args.res,))
    knobs = dict(bucket_sides=sides, max_batch=args.batch,
                 max_queue_depth=args.max_queue_depth,
                 bucket_queue_depth=args.bucket_queue_depth,
                 overload_policy=args.policy)
    knobs.update(overrides)
    return RouterConfig(**knobs)


def serve_fleet(args):
    """Serve a worker-process fleet behind the consistent-hash router
    until interrupted: ``--fleet N`` is ``--listen`` at fleet scale."""
    from repro.fleet import FleetRouter, FleetSupervisor, RouterThread

    host, port = (_parse_hostport(args.listen) if args.listen
                  else ("127.0.0.1", 8788))
    sup = FleetSupervisor(args.fleet, worker_args=_worker_args(args))
    print(f"spawning {args.fleet} workers...", flush=True)
    try:
        links = sup.start()
        router = FleetRouter(links, _router_config(args), host=host,
                             port=port, supervisor=sup)
        with RouterThread(router) as rt:
            workers = ", ".join(
                f"{l.name}=rpc:{l.rpc_port}" for l in links)
            print(f"yCHG fleet router on http://{host}:{rt.port} over "
                  f"{len(links)} workers ({workers})", flush=True)
            try:
                threading.Event().wait()
            except KeyboardInterrupt:
                print("shutting down fleet", flush=True)
    finally:
        sup.stop()


def fleet_smoke(args):
    """CI end-to-end assert for the fleet: router over 2 subprocess
    workers on loopback (ephemeral ports everywhere).

      1. **bit-identity** — a streamed batch through router -> worker RPC
         is byte-identical (values, dtypes, shapes) to the paper's NumPy
         baseline ``core.serial.analyze_numpy`` on the same masks (this
         process never touches JAX's backend: the workers own the chips);
      2. **rerouting** — hard-kill the worker owning one mask's keyspace;
         the repeat analyze fails over to the survivor, still matches,
         and ``ychg_fleet_rerouted_total`` moves;
      3. **peering** — restart the dead slot (same ring name, empty
         cache) and repeat the mask once more: the restarted owner
         adopts the survivor's cached entry instead of recomputing, and
         the rolled-up /metrics page shows
         ``ychg_cache_peer_hits_total`` > 0.

    Exits nonzero on any failure — the fleet-smoke CI job runs this.
    """
    import asyncio

    from repro.core import serial
    from repro.data import modis
    from repro.fleet import (
        FleetRouter,
        FleetSupervisor,
        HashRing,
        RouterThread,
    )
    from repro.fleet.router import routing_key
    from repro.frontend import YCHGClient

    def counter(text, name):
        for line in text.splitlines():
            if line.startswith(name + " "):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    def check_identical(leg, got, want_res):
        for field, arr in want_res.items():
            a, b = np.asarray(arr), got[field]
            if not (np.array_equal(a, b) and a.dtype == b.dtype
                    and a.shape == b.shape):
                raise SystemExit(f"fleet smoke [{leg}]: field {field!r} "
                                 f"not bit-identical through the router")

    masks = [modis.snowfield(args.res, seed=s) for s in range(args.batch)]
    want = [serial.analyze_numpy(m) for m in masks]

    sup = FleetSupervisor(2, worker_args=_worker_args(args))
    try:
        links = sup.start()
        # health loop effectively dormant: the smoke drives the death ->
        # reroute -> restart -> peer-hit sequence deterministically
        router = FleetRouter(links, _router_config(
            args, health_interval_s=3600.0), supervisor=sup)
        with RouterThread(router) as rt, \
                YCHGClient("127.0.0.1", rt.port) as client:
            client.wait_ready(timeout=120.0)
            items = {it.id: it for it in client.analyze_batch(masks)}
            for i, want_res in enumerate(want):
                item = items.get(i)
                if item is None or not item.ok:
                    raise SystemExit(
                        f"fleet smoke [identity]: mask {i} failed through "
                        f"the router: {item and item.error}")
                check_identical("identity", item.result, want_res)
            print(f"fleet smoke: {len(masks)} masks through router over 2 "
                  f"workers bit-identical to the NumPy reference",
                  flush=True)

            # trace leg: one traced batch, then merge the client-local,
            # router, and per-worker flight recorders and assert a single
            # trace id stitches spans across >= 2 processes in order
            if obs.tracing_enabled():
                tid = obs.new_trace_id()
                fresh = [modis.snowfield(args.res, seed=7000 + s)
                         for s in range(2)]
                for it in client.analyze_batch(fresh, trace_id=tid):
                    if not it.ok:
                        raise SystemExit(f"fleet smoke [trace]: traced "
                                         f"batch failed: {it.error}")
                events = list(obs.recorder().chrome_events())
                events += client.debug_traces().get("traceEvents", [])
                for l in links:
                    with YCHGClient(l.host, l.http_port) as wc:
                        events += wc.debug_traces().get("traceEvents", [])
                events = [e for e in events
                          if e.get("args", {}).get("trace_id") == tid]
                names = {e["name"] for e in events}
                needed = {"client.encode", "router.admission",
                          "router.forward", "frontend.parse",
                          "scheduler.queue_wait", "engine.compute"}
                if needed - names:
                    raise SystemExit(f"fleet smoke [trace]: spans missing "
                                     f"across the fleet recorders: "
                                     f"{sorted(needed - names)}")
                pids = {e["pid"] for e in events}
                if len(pids) < 2:
                    raise SystemExit(
                        f"fleet smoke [trace]: trace {tid} never crossed a "
                        f"process boundary (pids {sorted(pids)})")
                ts = {}
                for e in events:   # earliest start per span name
                    ts[e["name"]] = min(ts.get(e["name"], e["ts"]), e["ts"])
                slack_us = 100_000   # cross-process wall alignment slack
                chain = ["client.encode", "router.admission",
                         "frontend.parse", "engine.compute"]
                for a, b in zip(chain, chain[1:]):
                    if ts[b] + slack_us < ts[a]:
                        raise SystemExit(f"fleet smoke [trace]: span {b!r} "
                                         f"starts before {a!r}")
                import json as _json
                _json.loads(_json.dumps({"traceEvents": events}))
                print(f"fleet smoke: trace {tid} stitches "
                      f"{len(events)} spans across {len(pids)} processes "
                      f"(client -> router -> worker)", flush=True)

            ring = HashRing([l.name for l in links],
                            router.config.replicas)
            owner = ring.node_for(routing_key(masks[0]))
            owner_link = next(l for l in links if l.name == owner)
            owner_link.process.kill()
            owner_link.process.wait(timeout=30)
            check_identical("reroute", client.analyze(masks[0]), want[0])
            rerouted = counter(client.metrics_text(),
                               "ychg_fleet_rerouted_total")
            if rerouted < 1:
                raise SystemExit("fleet smoke [reroute]: killed the owner "
                                 "but ychg_fleet_rerouted_total never moved")
            print(f"fleet smoke: killed {owner}, request rerouted to the "
                  f"survivor and stayed bit-identical", flush=True)

            # one manual health pass: notices the corpse, restarts the
            # slot under its old name, re-broadcasts the peer set
            asyncio.run_coroutine_threadsafe(
                router.check_workers(), rt._loop).result(timeout=300)
            health = client.health()
            if not all(health["workers"].values()):
                raise SystemExit(f"fleet smoke [peering]: restart left "
                                 f"workers down: {health['workers']}")
            check_identical("peering", client.analyze(masks[0]), want[0])
            peer_hits = counter(client.metrics_text(),
                                "ychg_cache_peer_hits_total")
            if peer_hits < 1:
                raise SystemExit(
                    "fleet smoke [peering]: restarted owner served the "
                    "repeat mask without a sibling-cache hit "
                    f"(ychg_cache_peer_hits_total={peer_hits})")
            print(f"fleet smoke: restarted {owner} served repeat traffic "
                  f"from the survivor's cache (peer hits {peer_hits:.0f})",
                  flush=True)
    finally:
        sup.stop()


def _scene_manifest(args):
    from repro.scene import manifest_from_json, synthetic_manifest

    if args.manifest:
        with open(args.manifest) as f:
            return manifest_from_json(f.read())
    return synthetic_manifest(args.granules, args.scene_height,
                              args.scene_width, seed=args.seed)


def scene_run(args):
    """``serve.py ... scene``: run a granule manifest as a resumable bulk
    job. SIGTERM/SIGINT checkpoint the current tile row and exit cleanly;
    rerunning the same command resumes from the last checkpoint and the
    output files come out byte-identical to an uninterrupted run."""
    import signal

    from repro.engine import Engine
    from repro.scene import BulkJob, BulkJobConfig, SceneProgress

    manifest = _scene_manifest(args)
    cfg = BulkJobConfig(out_dir=args.out, ckpt_dir=args.ckpt,
                        tile_h=args.tile_h, stack_tiles=args.stack,
                        checkpoint_every=args.checkpoint_every)
    progress = SceneProgress()
    job = BulkJob(Engine(), manifest, cfg, progress=progress)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())

    px = sum(s.pixels for s in manifest)
    print(f"bulk job: {len(manifest)} granules "
          f"({px / 1e6:.1f} Mpx total), tile_h {cfg.tile_h}, "
          f"stacks of {cfg.stack_tiles}, checkpoint every "
          f"{cfg.checkpoint_every} stacks -> {args.ckpt}", flush=True)
    report = job.run(max_stacks=args.max_stacks, should_stop=stop.is_set)
    snap = progress.snapshot()
    done_px = report.tiles_done * cfg.tile_h * manifest[0].width
    rate = (done_px / report.elapsed_s / 1e6
            if report.elapsed_s > 0 else 0.0)
    print(f"bulk job {report.status}: {report.granules_done} granules, "
          f"{report.tiles_done} tiles in {report.elapsed_s:.2f}s "
          f"({rate:.0f} Mpx/s); tiles {snap.tiles_done}/{snap.tiles_total}, "
          f"resumes {report.resumes}, "
          f"stitch {snap.stitch_time_s * 1e3:.1f}ms", flush=True)
    for path in report.written:
        print(f"  wrote {path}", flush=True)
    dump = obs.auto_dump("scene-run-end")
    if dump:
        print(f"flight recorder dumped to {dump}", flush=True)
    if not report.completed:
        print("interrupted — rerun the same command to resume from the "
              "checkpoint", flush=True)


def scene_smoke(args):
    """CI end-to-end assert for the scene subsystem (repro.scene):

      1. **stitch bit-identity** — streaming a synthetic granule through
         ``SceneRunner`` (ragged last strip included) produces all seven
         result fields BIT-IDENTICAL (values, dtypes, shapes) to one
         whole-scene ``engine.analyze`` call;
      2. **kill -> resume byte-identity** — a ``BulkJob`` stopped
         mid-granule (with its newest checkpoint then truncated, so the
         Checkpointer must fall back to the previous valid one) resumes
         and writes result files byte-identical to an uninterrupted run;
      3. **online/offline agreement** — the same tiles replayed through
         the HTTP front end's NDJSON batch endpoint match per-tile
         ``engine.analyze`` bit for bit, ``stitch_tile_runs`` over the
         wire results equals the offline scene runs, and the attached
         ``SceneProgress`` surfaces in ``/metrics``.

    Exits nonzero on any failure — the scene-smoke CI job runs this.
    """
    import glob
    import os
    import tempfile
    import warnings

    from repro.data import scenes
    from repro.engine import Engine
    from repro.frontend import ServerThread, YCHGClient
    from repro.scene import (
        BulkJob,
        BulkJobConfig,
        GranuleReader,
        SceneProgress,
        SceneRunner,
        read_scene_result,
        stitch_tile_runs,
        synthetic_manifest,
    )
    from repro.service import ServiceConfig, YCHGService

    engine = Engine()

    # leg 1: stitch bit-identity, ragged last strip (45 = 3*16 - 3)
    h, w, tile_h = 45, args.res, 16
    mask = scenes.scene(h, w, seed=7, cell=8)
    reader = GranuleReader.from_array(mask, tile_h, granule_id="smoke")
    got = SceneRunner(engine, stack_tiles=2).analyze_scene(reader).to_host()
    want = engine.analyze(mask).to_host()
    for field, arr in want.items():
        a, b = np.asarray(arr), got[field]
        if not (np.array_equal(a, b) and a.dtype == b.dtype
                and a.shape == b.shape):
            raise SystemExit(f"scene smoke [stitch]: field {field!r} of the "
                             f"stitched result is not bit-identical to the "
                             f"whole-scene analysis")
    print(f"scene smoke: {reader.n_tiles} stitched strips of a {h}x{w} "
          f"scene bit-identical to one whole-scene call", flush=True)

    # leg 2: kill -> resume byte-identity through a corrupted checkpoint
    manifest = synthetic_manifest(2, 40, args.res, seed=3, cell=8)
    with tempfile.TemporaryDirectory() as tmp:
        def job(tag, progress=None):
            return BulkJob(engine, manifest, BulkJobConfig(
                out_dir=os.path.join(tmp, tag, "out"),
                ckpt_dir=os.path.join(tmp, tag, "ckpt"),
                tile_h=8, stack_tiles=1, checkpoint_every=1),
                progress=progress)

        straight = job("straight").run()
        if not straight.completed:
            raise SystemExit("scene smoke [resume]: uninterrupted run did "
                             "not complete")
        first = job("killed").run(max_stacks=3)
        if first.completed:
            raise SystemExit("scene smoke [resume]: max_stacks=3 should "
                             "have interrupted the job mid-granule")
        # hard-kill flavour: truncate the newest checkpoint's shard so the
        # resume must warn and fall back to the previous valid step
        steps = sorted(glob.glob(os.path.join(tmp, "killed", "ckpt",
                                              "step_*")))
        shard = glob.glob(os.path.join(steps[-1], "*.npz"))[0]
        with open(shard, "r+b") as f:
            f.truncate(8)
        progress = SceneProgress()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            second = job("killed", progress).run()
        if not any(issubclass(c.category, RuntimeWarning) for c in caught):
            raise SystemExit("scene smoke [resume]: truncated checkpoint "
                             "resumed without a RuntimeWarning fallback")
        if not second.completed or second.resumes < 1:
            raise SystemExit(f"scene smoke [resume]: resumed run ended "
                             f"{second.status} with {second.resumes} resumes")
        for spec in manifest:
            a = os.path.join(tmp, "straight", "out",
                             f"{spec.granule_id}.ychg")
            b = os.path.join(tmp, "killed", "out", f"{spec.granule_id}.ychg")
            with open(a, "rb") as fa, open(b, "rb") as fb:
                if fa.read() != fb.read():
                    raise SystemExit(
                        f"scene smoke [resume]: {spec.granule_id} output "
                        f"differs between straight and killed+resumed runs")
        offline = read_scene_result(os.path.join(
            tmp, "straight", "out", f"{manifest[0].granule_id}.ychg"))
        snap = progress.snapshot()
        print(f"scene smoke: kill at stack 3 + corrupt newest checkpoint, "
              f"resume wrote byte-identical outputs "
              f"(resumes {second.resumes}, tiles "
              f"{snap.tiles_done}/{snap.tiles_total})", flush=True)

        # leg 3: online/offline agreement over loopback NDJSON. Buckets are
        # square on max(h, w), so (tile_h, W) strips land in the W bucket.
        spec = manifest[0]
        reader = GranuleReader.open(spec, 8)
        tiles = [reader.read_tile(t) for t in range(reader.n_tiles)]
        svc_cfg = ServiceConfig(bucket_sides=(spec.width,),
                                max_batch=args.batch)
        with YCHGService(engine, svc_cfg) as svc, \
                ServerThread(svc) as srv, \
                YCHGClient("127.0.0.1", srv.port) as client:
            svc.attach_scene_progress(progress)
            items = {it.id: it for it in client.analyze_batch(tiles)}
            tile_runs = []
            for i, tile in enumerate(tiles):
                item = items.get(i)
                if item is None or not item.ok:
                    raise SystemExit(
                        f"scene smoke [online]: tile {i} failed over the "
                        f"wire: {item and item.error}")
                for field, arr in engine.analyze(tile).to_host().items():
                    a, b = np.asarray(arr), item.result[field]
                    if not (np.array_equal(a, b) and a.dtype == b.dtype
                            and a.shape == b.shape):
                        raise SystemExit(
                            f"scene smoke [online]: field {field!r} of "
                            f"tile {i} not bit-identical over the wire")
                tile_runs.append(item.result["runs"])
            online_runs = stitch_tile_runs(tile_runs, tiles)
            if not np.array_equal(online_runs, offline.runs):
                raise SystemExit(
                    "scene smoke [online]: stitching the wire-served tile "
                    "runs does not match the offline scene result")
            metrics = client.metrics_text()
        for needle in ("ychg_scene_tiles_done", "ychg_scene_resumes_total"):
            if needle not in metrics:
                raise SystemExit(f"scene smoke [online]: {needle!r} missing "
                                 f"from /metrics with a scene progress "
                                 f"attached")
        print(f"scene smoke: {len(tiles)} tiles over loopback NDJSON "
              f"bit-identical per tile, online stitch == offline scene "
              f"result, scene gauges on /metrics", flush=True)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("command", nargs="?", choices=["scene"],
                    help="optional subcommand: 'scene' runs a resumable "
                         "granule bulk job (repro.scene)")
    ap.add_argument("--workload", default="ychg", choices=["ychg", "lm"])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--res", type=int, default=1024)
    ap.add_argument("--op", default="ychg",
                    choices=["ychg", "ccl", "denoise"],
                    help="ychg workload only: which registered operator "
                         "the --workload/smoke masks run through")
    ap.add_argument("--op-smoke", action="store_true",
                    help="ychg only: multi-op loopback assert (per-op wire "
                         "bit-identity vs reference, pipeline == separate "
                         "requests, 404 on unknown op)")
    ap.add_argument("--overload", action="store_true",
                    help="ychg only: add a bounded-queue overload pass and "
                         "fail unless admission control sheds")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="ychg only: serve over HTTP until interrupted")
    ap.add_argument("--rpc-listen", default=None, metavar="HOST:PORT",
                    help="with --listen: also serve the framed TCP RPC")
    ap.add_argument("--connect", default=None, metavar="URL",
                    help="ychg only: run the workload against a running "
                         "front end (http://HOST:PORT)")
    ap.add_argument("--frontend-smoke", action="store_true",
                    help="ychg only: loopback HTTP end-to-end assert "
                         "(bit-identical round trip + 429 on overload)")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="ychg only: serve N worker processes behind the "
                         "consistent-hash router (with --listen for the "
                         "router's HOST:PORT)")
    ap.add_argument("--fleet-smoke", action="store_true",
                    help="ychg only: loopback fleet end-to-end assert "
                         "(bit-identity, kill-one-worker rerouting, "
                         "peered-cache hit)")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated bucket sides (default: --res)")
    ap.add_argument("--max-queue-depth", type=int, default=None)
    ap.add_argument("--bucket-queue-depth", type=int, default=None)
    ap.add_argument("--policy", default="block", choices=["block", "shed"],
                    help="overload policy for --listen/--frontend-smoke")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="dump the flight recorder (recent request traces) "
                         "as Chrome-trace JSON to PATH on shutdown; "
                         "plumbed to --fleet workers (each appends .<pid>)")
    ap.add_argument("--scene-smoke", action="store_true",
                    help="ychg only: scene subsystem end-to-end assert "
                         "(stitch bit-identity, kill->resume "
                         "byte-identity, online/offline agreement)")
    ap.add_argument("--slo-smoke", action="store_true",
                    help="ychg only: traffic-class loopback assert "
                         "(priority preemption, deadline shed with an "
                         "honest Retry-After, tenant-quota 429s)")
    scn = ap.add_argument_group("scene", "knobs for the 'scene' subcommand")
    scn.add_argument("--scene-height", type=int, default=2048)
    scn.add_argument("--scene-width", type=int, default=1024)
    scn.add_argument("--granules", type=int, default=2,
                     help="synthetic manifest size (ignored with --manifest)")
    scn.add_argument("--seed", type=int, default=0,
                     help="first synthetic granule's content seed")
    scn.add_argument("--manifest", default=None, metavar="JSON",
                     help="granule manifest file (repro.scene "
                          "manifest_to_json format) instead of synthetic")
    scn.add_argument("--tile-h", type=int, default=256,
                     help="strip height the scene is windowed into")
    scn.add_argument("--stack", type=int, default=4,
                     help="strips per device batch")
    scn.add_argument("--out", default="scene_out",
                     help="directory for <granule_id>.ychg results")
    scn.add_argument("--ckpt", default="scene_ckpt",
                     help="checkpoint directory (resume state lives here)")
    scn.add_argument("--checkpoint-every", type=int, default=4,
                     help="stacks between mid-granule checkpoints")
    scn.add_argument("--max-stacks", type=int, default=None,
                     help="stop (with a checkpoint) after N stacks")
    return ap


def main():
    args = build_parser().parse_args()
    if args.trace_dump:
        obs.configure(dump_path=args.trace_dump)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    def smoke(tag, fn):
        """Run a CI smoke leg; on ANY failure dump the flight recorder
        first (with --trace-dump, CI uploads it as a debugging artifact)
        and re-raise so the job still exits nonzero."""
        try:
            fn(args)
        except BaseException:
            path = obs.auto_dump(f"{tag}-failure")
            if path:
                print(f"{tag}: flight recorder dumped to {path}",
                      flush=True)
            raise

    if args.command == "scene":
        scene_run(args)
    elif args.scene_smoke:
        smoke("scene-smoke", scene_smoke)
    elif args.fleet_smoke:
        smoke("fleet-smoke", fleet_smoke)
    elif args.fleet:
        serve_fleet(args)
    elif args.op_smoke:
        smoke("op-smoke", op_smoke)
    elif args.frontend_smoke:
        smoke("frontend-smoke", frontend_smoke)
    elif args.slo_smoke:
        smoke("slo-smoke", slo_smoke)
    elif args.listen:
        serve_listen(args)
    elif args.connect:
        serve_connect(args)
    elif args.workload == "ychg":
        serve_ychg(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
