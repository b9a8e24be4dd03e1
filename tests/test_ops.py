"""Multi-op platform suite.

Covers the (op, platform) registry and the two new operators end to end:
  * typed op errors — unknown ops raise ``UnknownOpError`` naming the
    registered ops, never a bare ``KeyError``;
  * registration is live — a backend registered for an op after an engine
    was built wins the very next resolution (generation bump);
  * no platform fallback — an op whose backends claim no current platform
    raises a ``ValueError`` naming the platform; nothing is substituted;
  * ccl / denoise parity — jnp reference vs Pallas kernel bit-identical
    on ragged corpora, ccl vs a pure-Python BFS oracle, and both ops
    pad-invariant (zero padding never changes the native region);
  * pipelines — spec validation errors, and the device-resident compound
    request pinned bit-identical to issuing the stages as separate
    requests, at the engine AND service layers;
  * per-op serving — cache entries namespaced by op, per-op bucket
    ladders and max_batch from ``ServiceConfig``.
"""

import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.engine import (
    Engine,
    UnknownOpError,
    YCHGConfig,
    registry,
    resolve,
)
from repro.engine.ops import (
    get_op,
    op_names,
    pipeline_op_key,
    split_pipeline_key,
    validate_pipeline,
)
from repro.kernels import ccl as cclmod
from repro.kernels import denoise as dnmod
from repro.kernels.platform import VmemBudgetError
from repro.service import Service, ServiceConfig
from repro.service.cache import make_key


def _masks(shapes, seed=0, density=0.5):
    rng = np.random.default_rng(seed)
    return [(rng.random(s) < density).astype(np.uint8) for s in shapes]


RAGGED = [(1, 1), (1, 7), (6, 1), (17, 23), (20, 17), (33, 64)]


# ----------------------------------------------------------- op registry


def test_builtin_ops_registered_everywhere():
    assert {"ychg", "ccl", "denoise"} <= set(op_names())
    assert {"ychg", "ccl", "denoise"} <= set(registry.registered_ops())
    for op in ("ccl", "denoise"):
        assert set(registry.backend_names(op)) == {"jax", "pallas"}


def test_unknown_op_is_a_typed_error_naming_registered_ops():
    with pytest.raises(UnknownOpError, match="ychg"):
        get_op("warp")
    with pytest.raises(UnknownOpError, match="warp"):
        resolve("auto", platform="cpu", op="warp")
    # an engine surfaces the same typed error, not a KeyError
    with pytest.raises(UnknownOpError):
        Engine().analyze(np.zeros((4, 4), np.uint8), op="warp")


def test_register_backend_for_op_is_live_immediately():
    """Registering a higher-priority ccl backend after the engine resolved
    once must win the next resolution (resolve.cache_clear + generation
    bump), and unregistering restores the old pick."""
    fixed = cclmod.labels(jnp.ones((1, 2, 3), jnp.uint8))
    eng = Engine(YCHGConfig(backend="auto"))
    assert eng.resolve_backend(op="ccl") == "jax"   # prime caches
    gen = registry.generation()
    registry.register_backend(registry.BackendSpec(
        name="_test_ccl_stub", op="ccl", run=lambda x, c: fixed,
        supports_batch=True, supports_mesh=False, device_kinds=("cpu",),
        priority={"cpu": 999},
    ))
    try:
        assert registry.generation() > gen
        assert eng.resolve_backend(op="ccl") == "_test_ccl_stub"
        # the ychg namespace is untouched by a ccl registration
        assert "_test_ccl_stub" not in registry.backend_names("ychg")
    finally:
        registry.unregister_backend("_test_ccl_stub", op="ccl")
    assert eng.resolve_backend(op="ccl") == "jax"


def test_op_with_no_backend_for_platform_raises():
    """An op registered only for some other platform refuses to resolve
    on this one: a ValueError naming the platform, never a KeyError and
    never another platform's backend."""
    registry.register_backend(registry.BackendSpec(
        name="_test_tpu_only", op="_toyop",
        run=lambda x, c: cclmod.labels(x), supports_batch=True,
        supports_mesh=False, device_kinds=("tpu",), priority={"tpu": 10},
    ))
    try:
        with pytest.raises(ValueError, match="platform 'cpu'"):
            resolve("auto", platform="cpu", op="_toyop")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert resolve("auto", platform="tpu",
                           op="_toyop").name == "_test_tpu_only"
    finally:
        registry.unregister_backend("_test_tpu_only", op="_toyop")
    with pytest.raises(UnknownOpError):
        resolve("auto", platform="cpu", op="_toyop")


# ------------------------------------------------------------- ccl parity


def _bfs_labels(mask):
    """Pure-Python 4-neighbour CCL oracle: row-major first-encounter
    numbering, which is exactly the canonical min-linear-index rank."""
    h, w = mask.shape
    out = np.zeros((h, w), np.int32)
    n = 0
    for i in range(h):
        for j in range(w):
            if mask[i, j] and not out[i, j]:
                n += 1
                stack = [(i, j)]
                out[i, j] = n
                while stack:
                    y, x = stack.pop()
                    for dy, dx in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                        yy, xx = y + dy, x + dx
                        if (0 <= yy < h and 0 <= xx < w and mask[yy, xx]
                                and not out[yy, xx]):
                            out[yy, xx] = n
                            stack.append((yy, xx))
    return out, n


@pytest.mark.parametrize("shape", RAGGED)
def test_ccl_reference_matches_bfs_oracle(shape):
    (mask,) = _masks([shape], seed=sum(shape))
    got = cclmod.labels(jnp.asarray(mask)[None])
    want_lab, want_n = _bfs_labels(mask)
    np.testing.assert_array_equal(np.asarray(got.labels[0]), want_lab)
    assert int(got.n_components[0]) == want_n


def test_ccl_pallas_bit_identical_to_reference():
    rng = np.random.default_rng(3)
    stack = (rng.random((4, 24, 31)) < 0.5).astype(np.uint8)
    a = cclmod.labels(jnp.asarray(stack))
    b = cclmod.labels_pallas(jnp.asarray(stack))
    np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))
    np.testing.assert_array_equal(np.asarray(a.n_components),
                                  np.asarray(b.n_components))


def test_ccl_is_pad_invariant():
    """Zero padding to a larger canvas starts no components and never
    renumbers the native region (row-major first encounter preserved)."""
    (mask,) = _masks([(13, 19)], seed=5)
    base = cclmod.labels(jnp.asarray(mask)[None])
    padded = np.zeros((1, 20, 32), np.uint8)
    padded[0, :13, :19] = mask
    pad = cclmod.labels(jnp.asarray(padded))
    np.testing.assert_array_equal(np.asarray(pad.labels[0, :13, :19]),
                                  np.asarray(base.labels[0]))
    assert np.all(np.asarray(pad.labels)[0, 13:, :] == 0)
    assert np.all(np.asarray(pad.labels)[0, :, 19:] == 0)
    assert int(pad.n_components[0]) == int(base.n_components[0])


# --------------------------------------------------------- denoise parity


def test_denoise_pallas_bit_identical_to_reference():
    rng = np.random.default_rng(4)
    stack = rng.random((3, 22, 27)).astype(np.float32)
    a = dnmod.denoise(jnp.asarray(stack))
    b = dnmod.denoise_pallas(jnp.asarray(stack))
    np.testing.assert_array_equal(np.asarray(a.image), np.asarray(b.image))
    assert np.asarray(a.image).dtype == np.float32


@pytest.mark.parametrize("kernel, shape, block", [
    # ccl labels in bands of 8-row slabs; none fits a row this wide
    (cclmod.labels_pallas, (64, 400000), (1, 8, 400000)),
    (dnmod.denoise_pallas, (2048, 2048), (1, 2048, 2048)),
])
def test_oversize_whole_image_block_raises_named_error(kernel, shape, block):
    """A block no VMEM limit can hold is refused at trace time with an
    error naming the shape and the limit; no other backend stands in,
    and nothing is allocated."""
    big = jax.ShapeDtypeStruct((2, *shape), jnp.uint8)
    with pytest.raises(VmemBudgetError,
                       match=re.escape(str(block)) + ".*100 MiB limit"):
        jax.eval_shape(kernel, big)
    # one rung below the ladder's top still fits
    jax.eval_shape(kernel, jax.ShapeDtypeStruct((2, 1024, 1024), jnp.uint8))


def test_denoise_is_pad_invariant():
    """The 3x3 window zero-pads at borders, so padding the canvas with
    zeros reproduces the native region exactly."""
    rng = np.random.default_rng(6)
    img = rng.random((14, 18)).astype(np.float32)
    base = dnmod.denoise(jnp.asarray(img)[None])
    padded = np.zeros((1, 20, 24), np.float32)
    padded[0, :14, :18] = img
    pad = dnmod.denoise(jnp.asarray(padded))
    # interior rows/cols are window-identical; the former border rows see
    # a zero neighbourhood either way
    np.testing.assert_array_equal(np.asarray(pad.image[0, :13, :17]),
                                  np.asarray(base.image[0, :13, :17]))


# -------------------------------------------------- engine per-op dispatch


@pytest.mark.parametrize("op", ["ccl", "denoise"])
def test_engine_dispatches_new_ops_bit_identical(op):
    rng = np.random.default_rng(7)
    stack = (rng.random((5, 18, 25)) < 0.5).astype(np.uint8)
    eng = Engine()
    got = eng.analyze_batch(stack, op=op).to_host()
    spec = get_op(op)
    want = spec.from_summary(spec.reference(jnp.asarray(stack)), True)
    for field, arr in want.to_host().items():
        np.testing.assert_array_equal(got[field], np.asarray(arr),
                                      err_msg=field)


@pytest.mark.parametrize("op", ["ccl", "denoise"])
def test_engine_meshed_new_ops_bit_identical(op):
    from repro.sharding import make_batch_mesh

    rng = np.random.default_rng(8)
    stack = (rng.random((3, 16, 21)) < 0.5).astype(np.uint8)  # ragged vs mesh
    mesh = make_batch_mesh()
    eng = Engine(YCHGConfig(backend="auto"), mesh=mesh)
    got = eng.analyze_batch(stack, op=op)
    assert got.batch_size == 3
    spec = get_op(op)
    want = spec.from_summary(spec.reference(jnp.asarray(stack)), True)
    for field, arr in want.to_host().items():
        np.testing.assert_array_equal(got.to_host()[field], np.asarray(arr),
                                      err_msg=field)


# --------------------------------------------------------------- pipelines


def test_pipeline_spec_validation():
    assert validate_pipeline(["denoise", "ychg"]) == ("denoise", "ychg")
    assert pipeline_op_key(["denoise", "ychg"]) == "denoise+ychg"
    assert split_pipeline_key("denoise+ychg") == ("denoise", "ychg")
    assert split_pipeline_key("ychg") == ("ychg",)
    with pytest.raises(ValueError):
        validate_pipeline([])
    with pytest.raises(UnknownOpError):
        validate_pipeline(["denoise", "warp"])
    # ychg has no chain_field: it can only terminate a pipeline
    with pytest.raises(ValueError, match="terminal"):
        validate_pipeline(["ychg", "ccl"])


def test_engine_pipeline_equals_sequential_dispatch():
    rng = np.random.default_rng(9)
    stack = rng.random((4, 20, 28)).astype(np.float32)
    eng = Engine()
    piped = eng.run_pipeline(stack, ["denoise", "ychg"]).to_host()
    mid = eng.analyze_batch(stack, op="denoise")
    want = eng.analyze_batch(mid.image, op="ychg").to_host()
    for field, arr in want.items():
        np.testing.assert_array_equal(piped[field], np.asarray(arr),
                                      err_msg=field)


def test_service_pipeline_equals_separate_requests_ragged():
    """The compound request through the bucketed service — padded canvas,
    inter-stage re-zeroing — pinned bit-identical to feeding stage 1's
    cropped output back in as a fresh stage 2 request, across ragged
    shapes sharing one bucket."""
    rng = np.random.default_rng(10)
    imgs = [rng.random(s).astype(np.float32)
            for s in ((30, 30), (17, 25), (32, 9))]
    cfg = ServiceConfig(bucket_sides=(32,), max_batch=4, max_delay_ms=1.0)
    with Service(Engine(), cfg) as svc:
        for img in imgs:
            piped = svc.pipeline(img, ["denoise", "ychg"],
                                 timeout=600).to_host()
            mid = svc.submit(img, op="denoise").result(timeout=600)
            want = svc.submit(np.asarray(mid.to_host()["image"]),
                              op="ychg").result(timeout=600).to_host()
            for field, arr in want.items():
                np.testing.assert_array_equal(
                    np.asarray(piped[field]), np.asarray(arr), err_msg=field)


def test_pipeline_stage_spans_and_histograms_recorded():
    cfg = ServiceConfig(bucket_sides=(16,), max_batch=2)
    with Service(Engine(), cfg) as svc:
        svc.pipeline(np.random.default_rng(0).random((12, 12))
                     .astype(np.float32), ["denoise", "ychg"], timeout=600)
        m = svc.metrics()
    stages = {dict(labels).get("stage") for labels, _snap in m.stage_hists}
    assert {"pipeline.denoise", "pipeline.ychg"} <= stages


# ------------------------------------------------------------ per-op serving


def test_cache_entries_are_namespaced_by_op():
    (mask,) = _masks([(16, 16)], seed=11)
    cfg = YCHGConfig()
    assert make_key(mask, "jax", cfg, op="ychg") != \
        make_key(mask, "jax", cfg, op="ccl")
    with Service(Engine(), ServiceConfig(bucket_sides=(16,))) as svc:
        svc.submit(mask, op="ychg").result(timeout=600)
        svc.submit(mask, op="ccl").result(timeout=600)   # no cross-op hit
        m1 = svc.metrics()
        svc.submit(mask, op="ccl").result(timeout=600)   # same-op repeat
        m2 = svc.metrics()
    assert m1.cache_misses == 2 and m1.cache_hits == 0
    assert m2.cache_hits == 1


def test_per_op_bucket_ladder_and_max_batch():
    cfg = ServiceConfig(bucket_sides=(64, 128), max_batch=8,
                        op_bucket_sides=(("ccl", (32,)),),
                        op_max_batch=(("ccl", 2),))
    assert cfg.bucket_sides_for("ccl") == (32,)
    assert cfg.bucket_sides_for("ychg") == (64, 128)
    assert cfg.max_batch_for("ccl") == 2
    assert cfg.max_batch_for("ychg") == 8
    (mask,) = _masks([(20, 20)], seed=12)
    with Service(Engine(), cfg) as svc:
        svc.submit(mask, op="ccl").result(timeout=600)
        m = svc.metrics()
    # a 20x20 ccl request lands in ccl's own 32 ladder, not the default 64
    assert (1, 32, 32) in m.compiled_shapes


def test_submit_rejects_pipeline_keys_pointing_at_submit_pipeline():
    with Service(Engine(), ServiceConfig(bucket_sides=(16,))) as svc:
        with pytest.raises(ValueError, match="submit_pipeline"):
            svc.submit(np.zeros((8, 8), np.uint8), op="denoise+ychg")
