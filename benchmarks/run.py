"""Benchmark harness — one function per paper figure/claim.

Paper: Figure 1 has two panels: (left) runtime vs resolution, serial vs
parallel; (right) runtime vs hyperedge count at fixed resolution. Claims:
  C1 serial runtime linear to ~2000^2, inflecting above; crossover exists
  C2 parallel wins 2x-10x at high resolution
  C3 runtime invariant to hyperedge count (147 -> 4.1M)

Hardware note: the paper compares a GeForce 310M (16 CUDA cores) against an
i5-480M. This container is a single CPU core: "parallel" here is the
data-parallel formulation (vectorized JAX / Pallas-interpret); "serial" is
the paper's scalar column walk (core/serial.py). The speedup numbers are
therefore formulation speedups, not device speedups; curve *shapes* and the
invariance claim are the reproduction targets (EXPERIMENTS.md §Paper-claims).

Output: ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import time

import numpy as np

import jax

from repro.core import serial, ychg
from repro.data import modis
from repro.engine import Engine, YCHGConfig, get_backend
from repro.kernels import ops as kops
from repro.launch import roofline
from repro.launch.compilecache import enable_compile_cache


def _t(fn, *args, reps: int = 3, warmup: int = 1) -> float:
    """Median wall time (us) of fn(*args) with jax sync."""
    for _ in range(warmup):
        r = fn(*args)
        jax.block_until_ready(r) if hasattr(r, "block_until_ready") or isinstance(
            r, (jax.Array, tuple, dict)) else None
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn(*args)
        if isinstance(r, (jax.Array, tuple, dict)):
            jax.block_until_ready(r)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def bench_resolution_sweep() -> list[str]:
    """Figure 1 (left): runtime vs resolution, serial vs data-parallel."""
    rows = []
    jit_analyze = jax.jit(ychg.analyze)
    for res in (250, 500, 1000, 2000, 4000):
        img = modis.snowfield(res, seed=0)
        jimg = jax.device_put(img)
        t_par = _t(lambda x: jit_analyze(x).n_hyperedges, jimg)
        t_ser = _t(serial.analyze_numpy, img, reps=3)
        if res <= 500:
            t_scalar = _t(serial.analyze_scalar, img, reps=1, warmup=0)
            rows.append(f"ychg_scalar_res{res},{t_scalar:.1f},"
                        f"speedup_vs_parallel={t_scalar / t_par:.1f}x")
        rows.append(f"ychg_serial_res{res},{t_ser:.1f},")
        rows.append(f"ychg_parallel_res{res},{t_par:.1f},"
                    f"speedup={t_ser / t_par:.2f}x")
    return rows


def bench_hyperedge_sweep() -> list[str]:
    """Figure 1 (right): runtime vs hyperedge count at fixed resolution (C3)."""
    rows = []
    res = 2048
    jit_analyze = jax.jit(ychg.analyze)
    times = []
    for n in (147, 1_000, 10_000, 100_000, 1_000_000):
        img = jax.device_put(modis.striped(res, n))
        t = _t(lambda x: jit_analyze(x).n_hyperedges, img)
        times.append(t)
        rows.append(f"ychg_hyperedges_{n},{t:.1f},n_hyperedges={n}")
    spread = max(times) / min(times)
    rows.append(f"ychg_hyperedge_invariance,{np.mean(times):.1f},"
                f"max_over_min={spread:.3f}")
    return rows


def bench_kernel_colscan() -> list[str]:
    """Step-1 kernel (Pallas interpret on CPU) vs jnp production path."""
    rows = []
    img = modis.snowfield(1024, seed=1)
    jimg = jax.device_put(img)
    t_jnp = _t(lambda x: ychg.column_runs(x), jimg)
    t_pal = _t(lambda x: kops.colscan_runs(x), jimg)
    rows.append(f"kernel_colscan_jnp_1024,{t_jnp:.1f},")
    rows.append(f"kernel_colscan_pallas_interp_1024,{t_pal:.1f},"
                "note=interpret-mode-correctness-only")
    return rows


def bench_fused_batch_sweep() -> list[str]:
    """Fused single-launch batched kernel vs the two-pass Pallas pipeline vs
    pure jnp, over batch size x resolution (the paper's serial/parallel
    crossover, measured as a curve).

    Launch accounting (the fusion claim): the fused pipeline issues ONE
    pallas_call per batch; the two-pass pipeline issues two per image
    (step-1 colscan + step-2 diff after an HBM round-trip of the counts
    vector), i.e. 2*B per batch. The serial column walk (core/serial.py)
    anchors the crossover threshold.
    """
    rows = []
    eng_fused = Engine(YCHGConfig(backend="fused"))
    for res in (128, 256, 512):
        for bsz in (1, 8, 32):
            imgs = np.stack([modis.snowfield(res, seed=s) for s in range(bsz)])
            jimgs = jax.device_put(imgs)

            def two_pass(x):
                # tuple so _t's block_until_ready sees and syncs the results
                return tuple(kops.analyze(x[i])["n_hyperedges"] for i in range(bsz))

            t_fused = _t(lambda x: eng_fused.analyze_batch(x).n_hyperedges, jimgs)
            t_two = _t(two_pass, jimgs)
            t_jnp = _t(lambda x: ychg.analyze_jit(x).n_hyperedges, jimgs)
            t_ser = _t(
                lambda x: [serial.analyze_numpy(x[i]) for i in range(bsz)], imgs
            )
            rows.append(f"ychg_fused_b{bsz}_res{res},{t_fused:.1f},launches=1")
            rows.append(
                f"ychg_twopass_b{bsz}_res{res},{t_two:.1f},launches={2 * bsz}"
            )
            rows.append(
                f"ychg_jnp_b{bsz}_res{res},{t_jnp:.1f},"
                f"fused_vs_twopass={t_two / t_fused:.2f}x"
            )
            rows.append(
                f"ychg_serial_b{bsz}_res{res},{t_ser:.1f},"
                f"fused_vs_serial={t_ser / t_fused:.2f}x"
            )
    return rows


def bench_engine_dispatch() -> list[str]:
    """Per-call overhead of the Engine dispatch layer.

    The engine's acceptance bar is <= 5 us/call over invoking the backend
    callable directly. Real kernels jitter by tens of us per call in
    interpret mode, which swamps a few-us delta, so the overhead row is
    measured against a registered *null* backend (returns a precomputed
    summary): the engine-vs-direct difference is then pure dispatch —
    ingest + registry resolution + result wrapping. The fused/jax rows give
    the real-path per-call context the overhead sits on top of.
    """
    from repro.engine import registry

    def per_call_us(fn, calls: int, trials: int = 5) -> float:
        # total-over-calls, best of trials: per-call medians cannot resolve
        # a few-us delta
        fn(), fn()
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(calls):
                r = fn()
            jax.block_until_ready(r)
            best = min(best, (time.perf_counter() - t0) / calls * 1e6)
        return best

    rows = []
    imgs = np.stack([modis.snowfield(64, seed=s) for s in range(4)])
    jimgs = jax.device_put(imgs)

    fixed = jax.block_until_ready(ychg.analyze_jit(jimgs))
    registry.register_backend(registry.BackendSpec(
        name="_bench_null", run=lambda x, c: fixed, supports_batch=True,
        supports_mesh=False, device_kinds=("cpu", "gpu", "tpu"),
    ))
    try:
        eng = Engine(YCHGConfig(backend="_bench_null"))
        direct, cfg = get_backend("_bench_null").run, eng.config
        t_direct = per_call_us(lambda: direct(jimgs, cfg).n_hyperedges,
                               calls=10000)
        t_engine = per_call_us(lambda: eng.analyze_batch(jimgs).n_hyperedges,
                               calls=10000)
    finally:
        # the stub must not outlive the bench: it would pollute
        # backend_names()/auto-resolution for everything after it in main()
        registry.unregister_backend("_bench_null")
    rows.append(f"engine_dispatch_overhead,{t_engine - t_direct:.2f},"
                f"null_backend_isolated_budget_us=5")

    for backend in ("fused", "jax"):
        beng = Engine(YCHGConfig(backend=backend))
        t_real = per_call_us(
            lambda: beng.analyze_batch(jimgs).n_hyperedges, calls=100)
        rows.append(f"engine_dispatch_engine_{backend},{t_real:.1f},"
                    f"real_path_context")
    return rows


def _mem_bound_us(nbytes: int) -> str:
    """HBM-bound time of a scan over ``nbytes`` on this run's device, from
    the peak table keyed by device_kind (an unknown TPU kind raises)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return f"mem_bound_us=n/a_on_{dev.platform}"
    bw = roofline.peaks(dev.device_kind).hbm_bw
    return f"mem_bound_us={nbytes / bw * 1e6:.1f}"


def bench_kernel_packed() -> list[str]:
    """§Perf iteration on the paper's kernel: 1-bit row packing (8x less HBM
    traffic on the memory-bound scan). Wall time + the device's HBM-bound
    time both reported; correctness asserted inline."""
    from repro.kernels.ychg_packed import pack_rows, packed_analyze

    rows = []
    res = 4096
    img = modis.snowfield(res, seed=2)
    jimg = jax.device_put(img)
    base = jax.jit(ychg.analyze)
    n_base = int(base(jimg).n_hyperedges)
    n_pack = int(packed_analyze(jimg)["n_hyperedges"])
    assert n_base == n_pack, (n_base, n_pack)
    packed = jax.block_until_ready(pack_rows(jimg))
    t_unpacked = _t(lambda x: base(x).n_hyperedges, jimg)
    t_packed_jit = _t(
        lambda x: packed_analyze(x)["n_hyperedges"], jimg
    )
    # the memory term dominates both: mask bytes over HBM bandwidth
    rows.append(f"ychg_kernel_baseline_4096,{t_unpacked:.1f},"
                f"{_mem_bound_us(res * res)}")
    rows.append(f"ychg_kernel_bitpacked_4096,{t_packed_jit:.1f},"
                f"{_mem_bound_us(res * res // 8)}_(8x_less_traffic)")
    return rows


def bench_lm_train_microstep() -> list[str]:
    """Tiny LM train step (the framework's hot loop on this box)."""
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.train.step import make_train_step

    cfg = ModelConfig(
        name="bench-tiny", family="dense", num_layers=4, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
        param_dtype="float32", activation_dtype="float32", remat="none",
        attn_chunk=128,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jax.device_put(rng.integers(0, 512, (8, 128)).astype(np.int32)),
        "labels": jax.device_put(rng.integers(0, 512, (8, 128)).astype(np.int32)),
    }
    t = _t(lambda p, o, b: step(p, o, b)[2]["loss"], params, opt, batch)
    toks = 8 * 128
    return [f"lm_train_microstep_1M,{t:.1f},tokens_per_s={toks / (t / 1e6):.0f}"]


def bench_serve_decode() -> list[str]:
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.models import init_cache, init_params
    from repro.train.step import make_serve_step

    cfg = ModelConfig(
        name="bench-decode", family="dense", num_layers=4, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
        param_dtype="float32", activation_dtype="float32", remat="none",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_cache(cfg, 8, 256)
    step = jax.jit(make_serve_step(cfg))
    tok = jax.device_put(np.ones((8, 1), np.int32))
    t = _t(lambda: step(params, cache, tok, jnp.int32(5))[0])
    return [f"lm_serve_decode_b8,{t:.1f},tokens_per_s={8 / (t / 1e6):.0f}"]


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    for fn in (
        bench_resolution_sweep,
        bench_hyperedge_sweep,
        bench_kernel_colscan,
        bench_fused_batch_sweep,
        bench_engine_dispatch,
        bench_kernel_packed,
        bench_lm_train_microstep,
        bench_serve_decode,
    ):
        for row in fn():
            print(row, flush=True)


if __name__ == "__main__":
    main()
