"""Engine / registry suite.

Covers the engine acceptance bar:
  * every registered backend is bit-identical to ``core.ychg.analyze`` on
    the seeded corpus (single image AND batched, through the engine);
  * ``backend="auto"`` resolution is a pure function of the registry
    (jax on CPU, fused on a fake-TPU capability entry, fused under a mesh);
  * results are device-resident pytrees — the fused/jax paths trace under
    ``jit`` (any implicit device->host copy would raise);
  * the mesh path strips blank-image padding internally for non-divisible
    batch sizes (4-device subprocess regression);
  * the deprecated ``core.api.analyze_image`` shim still returns the exact
    legacy dict and warns.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import serial, ychg
from repro.engine import (
    YCHGConfig,
    Engine,
    YCHGResult,
    backend_names,
    get_backend,
    registry,
    resolve,
)
from repro.kernels import ops as kops
from ychg_invariants import assert_bit_identical, random_masks, structured_masks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_BACKENDS = ("jax", "fused", "pallas", "serial", "scalar")


def _corpus():
    return structured_masks() + random_masks(8)


# ----------------------------------------------------------------- registry


def test_registry_has_all_builtin_backends():
    assert set(ALL_BACKENDS) <= set(backend_names())


def test_auto_resolution_cpu_picks_jax():
    assert resolve("auto", platform="cpu").name == "jax"


def test_auto_resolution_fake_tpu_picks_fused():
    """No TPU in CI: the registry's tpu capability entry drives resolution."""
    assert resolve("auto", platform="tpu").name == "fused"


def test_auto_resolution_with_mesh_picks_mesh_capable():
    assert resolve("auto", platform="cpu", need_mesh=True).supports_mesh
    assert resolve("auto", platform="cpu", need_mesh=True).name == "fused"


def test_resolution_rejects_unknown_and_meshless():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve("nope", platform="cpu")
    with pytest.raises(ValueError, match="does not support mesh"):
        resolve("serial", platform="cpu", need_mesh=True)


def test_register_backend_validates_priority_kinds():
    with pytest.raises(ValueError, match="device_kinds"):
        registry.register_backend(registry.BackendSpec(
            name="bogus", run=lambda x, c: None, supports_batch=True,
            supports_mesh=False, device_kinds=("cpu",), priority={"tpu": 1},
        ))
    assert "bogus" not in backend_names()


def test_register_unregister_roundtrip_and_cache_invalidation():
    """A registered backend is live immediately (even for engines built
    earlier) and gone after unregister — the generation counter invalidates
    both the lru_cache and per-engine spec caches."""
    fixed = ychg.analyze(jnp.ones((1, 2, 3), jnp.uint8))
    eng = Engine(YCHGConfig(backend="auto"))
    assert eng.resolve_backend() == "jax"  # prime the instance cache
    registry.register_backend(registry.BackendSpec(
        name="_test_stub", run=lambda x, c: fixed, supports_batch=True,
        supports_mesh=False, device_kinds=("cpu",), priority={"cpu": 999},
    ))
    try:
        assert "_test_stub" in backend_names()
        assert eng.resolve_backend() == "_test_stub"  # cache invalidated
    finally:
        registry.unregister_backend("_test_stub")
    assert "_test_stub" not in backend_names()
    assert eng.resolve_backend() == "jax"
    registry.unregister_backend("_test_stub")  # unknown name: no-op


def test_engine_resolves_per_platform():
    assert Engine().resolve_backend() == (
        "fused" if jax.default_backend() == "tpu" else "jax"
    )


# ----------------------------------------------------- backend parity suite


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_parity_on_corpus(backend):
    """Every registered backend, through the engine, bit-identical to the
    core.ychg oracle on the seeded corpus."""
    engine = Engine(YCHGConfig(backend=backend))
    for img in _corpus():
        want = ychg.analyze(jnp.asarray(img))
        got = engine.analyze(img).to_summary()
        assert_bit_identical(got, want)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_parity_batched(backend):
    rng = np.random.default_rng(42)
    imgs = (rng.random((5, 21, 34)) < 0.5).astype(np.uint8)
    engine = Engine(YCHGConfig(backend=backend))
    assert_bit_identical(engine.analyze_batch(imgs).to_summary(),
                         ychg.analyze(jnp.asarray(imgs)))


def test_single_image_is_b1_view():
    """analyze is the batched path with B=1 — not a separate code path."""
    rng = np.random.default_rng(0)
    img = (rng.random((19, 27)) < 0.5).astype(np.uint8)
    engine = Engine()
    one = engine.analyze(img)
    batch = engine.analyze_batch(img[None])
    assert one.runs.shape == batch.runs.shape == (1, 27)
    assert not one.batched and batch.batched
    np.testing.assert_array_equal(np.asarray(one.runs), np.asarray(batch.runs))


# ------------------------------------------------------- result pytree/host


def test_result_is_registered_pytree():
    rng = np.random.default_rng(1)
    imgs = (rng.random((3, 9, 13)) < 0.5).astype(np.uint8)
    res = Engine().analyze_batch(imgs)
    leaves, treedef = jax.tree_util.tree_flatten(res)
    assert len(leaves) == 7
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt, YCHGResult) and rebuilt.batched
    mapped = jax.tree_util.tree_map(lambda x: x, res)
    assert mapped.batched == res.batched  # static aux survives tree_map


@pytest.mark.parametrize("backend", ["jax", "fused"])
def test_device_backends_trace_under_jit(backend):
    """Device residency: any implicit np.asarray/device->host copy inside
    the engine would raise TracerArrayConversionError here."""
    rng = np.random.default_rng(2)
    imgs = jnp.asarray((rng.random((2, 17, 23)) < 0.5).astype(np.uint8))
    engine = Engine(YCHGConfig(backend=backend))
    res = jax.jit(engine.analyze_batch)(imgs)
    assert_bit_identical(res.to_summary(), ychg.analyze(imgs))


def test_results_stay_on_device():
    rng = np.random.default_rng(3)
    img = (rng.random((11, 29)) < 0.5).astype(np.uint8)
    res = Engine(YCHGConfig(backend="fused")).analyze(jnp.asarray(img))
    for leaf in jax.tree_util.tree_leaves(res):
        assert isinstance(leaf, jax.Array)


def test_to_host_matches_legacy_dict_form():
    rng = np.random.default_rng(4)
    img = (rng.random((31, 15)) < 0.5).astype(np.uint8)
    d = Engine().analyze(img).to_host()
    s = ychg.analyze(jnp.asarray(img))
    assert set(d) == {"runs", "cut_vertices", "transitions", "births",
                      "deaths", "n_hyperedges", "n_transitions"}
    for k in d:
        assert isinstance(d[k], np.ndarray)
        w = np.asarray(getattr(s, k))
        assert d[k].dtype == w.dtype and d[k].shape == w.shape
        np.testing.assert_array_equal(d[k], w, err_msg=k)


# ------------------------------------------------------------ verbs / config


def test_analyze_rejects_wrong_rank():
    engine = Engine()
    with pytest.raises(ValueError, match=r"\(H, W\)"):
        engine.analyze(np.zeros((2, 3, 4), np.uint8))
    with pytest.raises(ValueError, match=r"\(B, H, W\)"):
        engine.analyze_batch(np.zeros((3, 4), np.uint8))


def test_analyze_stream_mixed_items():
    rng = np.random.default_rng(5)
    img = (rng.random((12, 18)) < 0.5).astype(np.uint8)
    stack = (rng.random((3, 12, 18)) < 0.5).astype(np.uint8)
    engine = Engine()
    outs = list(engine.analyze_stream(iter([img, stack])))
    assert [o.runs.shape for o in outs] == [(1, 18), (3, 18)]
    assert_bit_identical(outs[1].to_summary(), ychg.analyze(jnp.asarray(stack)))


def test_config_is_frozen_and_hashable():
    cfg = YCHGConfig(backend="fused", block_w=64)
    assert hash(cfg) == hash(YCHGConfig(backend="fused", block_w=64))
    with pytest.raises(Exception):
        cfg.backend = "jax"  # type: ignore[misc]


def test_config_stream_vmem_budget_routes_to_streamed():
    """The engine's streaming threshold reaches the fused kernel dispatch."""
    rng = np.random.default_rng(6)
    imgs = (rng.random((2, 70, 150)) < 0.5).astype(np.uint8)
    engine = Engine(YCHGConfig(backend="fused", stream_vmem_budget=1,
                                   block_h=32))
    assert_bit_identical(engine.analyze_batch(imgs).to_summary(),
                         ychg.analyze(jnp.asarray(imgs)))


def test_interpret_true_is_refused_on_a_tpu(monkeypatch):
    """On a TPU the kernels compile; asking the engine to interpret them
    there is refused at construction, while None (auto) is accepted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        Engine(YCHGConfig(interpret=True))
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        Engine().with_config(interpret=True)
    assert Engine(YCHGConfig(interpret=None)).config.interpret is None


def test_config_dtype_casts_on_ingest():
    img = np.array([[0, 2], [3, 0]], np.int64)
    res = Engine(YCHGConfig(dtype="uint8")).analyze(img)
    assert_bit_identical(res.to_summary(),
                         ychg.analyze(jnp.asarray(img.astype(np.uint8))))


def test_workload_config_engine_section():
    from repro.configs.ychg_modis import config as workload_config

    wl = workload_config()
    cfg = wl.engine.to_engine_config(backend="fused")
    assert isinstance(cfg, YCHGConfig) and cfg.backend == "fused"
    assert cfg.block_w == wl.block_w and cfg.block_h == wl.block_h
    rng = np.random.default_rng(7)
    img = (rng.random((16, 24)) < 0.5).astype(np.uint8)
    assert_bit_identical(Engine(cfg).analyze(img).to_summary(),
                         ychg.analyze(jnp.asarray(img)))


# -------------------------------------------------------------- mesh path


def test_mesh_path_single_device_parity():
    from repro.sharding import make_batch_mesh

    rng = np.random.default_rng(8)
    imgs = (rng.random((5, 33, 40)) < 0.5).astype(np.uint8)
    engine = Engine(YCHGConfig(backend="auto"), mesh=make_batch_mesh())
    assert engine.resolve_backend() == "fused"
    res = engine.analyze_batch(imgs)
    assert res.batch_size == 5
    assert_bit_identical(res.to_summary(), ychg.analyze(jnp.asarray(imgs)))


def test_mesh_axis_mismatch_raises():
    from repro.sharding import make_batch_mesh

    with pytest.raises(ValueError, match="mesh_axis"):
        Engine(YCHGConfig(mesh_axis="batch"), mesh=make_batch_mesh("data"))


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    )
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import ychg
    from repro.engine import Engine, YCHGConfig
    from repro.sharding import make_batch_mesh

    mesh = make_batch_mesh()
    assert mesh.size == 4, mesh
    rng = np.random.default_rng(0)
    imgs = (rng.random((5, 17, 33)) < 0.5).astype(np.uint8)  # 5 % 4 != 0
    engine = Engine(YCHGConfig(backend="fused"), mesh=mesh)
    res = engine.analyze_batch(jnp.asarray(imgs))
    # padding to 8 must be stripped internally: callers see B=5
    assert res.batch_size == 5, res.runs.shape
    want = ychg.analyze(jnp.asarray(imgs))
    for f in ("runs", "births", "deaths", "n_hyperedges", "n_transitions"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res.to_summary(), f)),
            np.asarray(getattr(want, f)), err_msg=f)
    print("MESH-OK")
""")


def test_mesh_path_nondivisible_batch_subprocess():
    """Regression: non-divisible batch over a real 4-device mesh — the
    engine pads to the mesh size and strips the pad before returning.
    Subprocess because the host device count locks at first jax init."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "MESH-OK" in r.stdout, (
        r.stdout[-2000:] + r.stderr[-2000:])


# ---------------------------------------------------------- legacy shims


def _legacy_analyze_image(img, backend):
    """The pre-engine implementation of core.api.analyze_image, verbatim."""
    def summary_to_dict(s):
        return {
            "runs": np.asarray(s.runs),
            "cut_vertices": np.asarray(s.cut_vertices),
            "transitions": np.asarray(s.transitions),
            "births": np.asarray(s.births),
            "deaths": np.asarray(s.deaths),
            "n_hyperedges": np.asarray(s.n_hyperedges),
            "n_transitions": np.asarray(s.n_transitions),
        }

    if backend == "jax":
        return summary_to_dict(ychg.analyze_jit(img))
    if backend == "fused":
        return summary_to_dict(kops.analyze_fused(np.asarray(img)))
    if backend == "pallas":
        return {k: np.asarray(v) for k, v in kops.analyze(img).items()}
    if backend == "serial":
        return serial.analyze_numpy(np.asarray(img))
    return serial.analyze_scalar(np.asarray(img))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_analyze_image_shim_equivalence(backend):
    """The deprecated shim returns the exact legacy dict (keys, dtypes,
    values) and emits DeprecationWarning."""
    from repro.core.api import analyze_image

    rng = np.random.default_rng(9)
    img = (rng.random((23, 37)) < 0.5).astype(np.uint8)
    with pytest.warns(DeprecationWarning):
        got = analyze_image(img, backend=backend)
    want = _legacy_analyze_image(img, backend)
    assert set(got) == set(want)
    for k in want:
        w = np.asarray(want[k])
        assert got[k].dtype == w.dtype, k
        assert got[k].shape == w.shape, k
        np.testing.assert_array_equal(got[k], w, err_msg=k)


def test_analyze_image_unknown_backend_message():
    from repro.core.api import BACKENDS, analyze_image

    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="unknown backend"):
            analyze_image(np.zeros((2, 2), np.uint8), backend="cuda")
    assert BACKENDS == ALL_BACKENDS


def test_ychg_engine_shim_warns_and_agrees():
    """`YCHGEngine` is a deprecation shim over the op-dispatching
    `Engine`: construction warns, behaviour (op, results, backend
    resolution) is exactly ``Engine()``'s."""
    from repro.engine import YCHGEngine

    rng = np.random.default_rng(12)
    img = (rng.random((19, 27)) < 0.5).astype(np.uint8)
    with pytest.warns(DeprecationWarning, match="YCHGEngine is deprecated"):
        shim = YCHGEngine()
    eng = Engine()
    assert isinstance(shim, Engine)
    assert shim.op == eng.op == "ychg"
    assert shim.resolve_backend() == eng.resolve_backend()
    assert_bit_identical(shim.analyze(img).to_summary(),
                         eng.analyze(img).to_summary())


def test_batch_sharded_analyze_shim_warns_and_agrees():
    from repro.sharding import batch_sharded_analyze

    rng = np.random.default_rng(10)
    imgs = (rng.random((3, 14, 22)) < 0.5).astype(np.uint8)
    with pytest.warns(DeprecationWarning):
        got = batch_sharded_analyze(jnp.asarray(imgs))
    assert_bit_identical(got, ychg.analyze(jnp.asarray(imgs)))


def test_ychg_stats_accepts_engine():
    from repro.data.pipeline import ychg_stats

    rng = np.random.default_rng(11)
    masks = (rng.random((4, 16, 20)) < 0.4).astype(np.uint8)
    via_engine = ychg_stats(masks, engine=Engine(YCHGConfig(backend="fused")))
    via_legacy = ychg_stats(masks, backend="jnp")
    for k in via_legacy:
        np.testing.assert_array_equal(via_engine[k], via_legacy[k], err_msg=k)


def test_fused_backend_accepts_device_arrays_without_host_copy():
    """Satellite regression: the old api forced np.asarray(img) before the
    fused kernel. The fused backend callable must consume a jax.Array
    as-is — tracing it proves no host round-trip exists on the path."""
    cfg = YCHGConfig(backend="fused")
    run = get_backend("fused").run
    rng = np.random.default_rng(12)
    imgs = jnp.asarray((rng.random((2, 9, 17)) < 0.5).astype(np.uint8))
    out = jax.jit(lambda x: run(x, cfg).n_hyperedges)(imgs)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ychg.analyze(imgs).n_hyperedges))


# ------------------------------------------------------------ word ingest


@pytest.fixture
def words_at_any_size(monkeypatch):
    """Ship masks of any size as words (the tests' masks are below the
    floor the chip measured)."""
    from repro.kernels import ingest

    monkeypatch.setattr(ingest, "MIN_BYTES", 1)


def _bytes(shape, dtype, seed=0):
    """Random bytes of every value, as ``dtype`` (int8 holds negatives)."""
    raw = np.random.default_rng(seed).integers(0, 256, shape, dtype=np.uint8)
    return raw.view(dtype)


@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
@pytest.mark.parametrize("shape", [(3, 37, 260), (37, 260), (1, 1, 4),
                                   (2, 40, 1028), (12, 16)])
def test_word_ingest_is_bit_identical(dtype, shape, monkeypatch,
                                     words_at_any_size):
    """A C-contiguous byte mask whose width is a multiple of 4 goes as
    words (odd H, W/4 not a multiple of 128 included) and lands as the
    very array the old path made: dtype, (B, H, W) shape, every byte."""
    from repro.engine import engine as engine_mod

    shipped = []
    ship = engine_mod.ingest.ship
    monkeypatch.setattr(engine_mod.ingest, "ship",
                        lambda w, *a, **k: shipped.append(w.shape) or
                        ship(w, *a, **k))
    host = _bytes(shape, dtype, seed=sum(shape))
    x = Engine()._ingest(host, single=len(shape) == 2)
    want = host if len(shape) == 3 else host[None]
    assert x.dtype == want.dtype and x.shape == want.shape
    np.testing.assert_array_equal(np.asarray(x), want)
    assert shipped == [(want.shape[0] * want.shape[1], want.shape[2] // 4)]


@pytest.mark.parametrize("case", ["width5", "strided", "bool", "float32",
                                  "fortran", "device", "below_floor"])
def test_inputs_outside_the_gate_take_the_old_path(case, monkeypatch):
    from repro.engine import engine as engine_mod

    def refuse(*_a, **_k):
        raise AssertionError("shipped as words")

    monkeypatch.setattr(engine_mod.ingest, "ship", refuse)
    if case != "below_floor":
        monkeypatch.setattr(engine_mod.ingest, "MIN_BYTES", 1)
    base = _bytes((2, 9, 16), np.uint8, seed=3)
    host = {"width5": _bytes((2, 9, 5), np.uint8),
            "strided": base[:, :, ::2],         # width 8, not contiguous
            "bool": base > 127,
            "float32": base.astype(np.float32),
            "fortran": np.asfortranarray(base),
            "device": jnp.asarray(base), "below_floor": base}[case]
    x = Engine()._ingest(host)
    want = jnp.asarray(host)
    assert x.dtype == want.dtype and x.shape == want.shape
    np.testing.assert_array_equal(np.asarray(x), np.asarray(want))


def _reference(op, stack):
    from repro.engine import ops as engine_ops

    return engine_ops.get_op(op).reference(jnp.asarray(stack))


@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
@pytest.mark.parametrize("verb", ["analyze", "analyze_batch.ychg",
                                  "analyze_batch.ccl", "analyze_batch.denoise",
                                  "analyze_stream", "run_pipeline"])
def test_verbs_on_word_ingest_match_the_reference(verb, dtype,
                                                  words_at_any_size):
    from repro.engine import ops as engine_ops

    stack = _bytes((2, 11, 20), dtype, seed=8)
    if dtype == np.uint8:
        stack = (stack > 127).astype(np.uint8)     # a 0/1 mask
    engine = Engine()
    if verb == "analyze":
        got, op = [engine.analyze(stack[0])], "ychg"
        wants = [_reference(op, stack[:1])]
    elif verb.startswith("analyze_batch"):
        op = verb.split(".")[1]
        got, wants = [engine.analyze_batch(stack, op=op)], [
            _reference(op, stack)]
    elif verb == "analyze_stream":
        op = "ychg"
        got = list(engine.analyze_stream([stack[0], stack]))
        wants = [_reference(op, stack[:1]), _reference(op, stack)]
    else:
        op = "ychg"
        got = [engine.run_pipeline(stack, ["denoise", "ychg"])]
        wants = [_reference(op, _reference("denoise", stack).image)]
    for res, want in zip(got, wants):
        for f in engine_ops.get_op(op).fields:
            g, w = np.asarray(getattr(res.to_summary(), f)), np.asarray(
                getattr(want, f))
            if verb in ("analyze", "analyze_stream") and g.ndim < w.ndim:
                w = w[0]                        # the B=1 view of a lone mask
            assert g.dtype == w.dtype, f
            np.testing.assert_array_equal(g, w, err_msg=f)


# ------------------------------------------------------------ stage spans


@pytest.fixture
def traced():
    from repro import obs

    obs.configure(enabled=True)
    obs.recorder().clear()
    yield obs
    obs.configure(enabled=True)
    obs.recorder().clear()


def _recorded(obs):
    return [(tr.process, name, meta)
            for tr in obs.recorder().traces()
            for name, _, _, meta in tr.spans()]


def test_bare_analyze_to_host_records_put_dispatch_fetch(traced,
                                                        words_at_any_size):
    img = (np.random.default_rng(5).random((12, 20)) < 0.5).astype(np.uint8)
    Engine().analyze(img).to_host()
    got = _recorded(traced)     # a trace of its own for each
    assert [(p, n) for p, n, _ in got] == [
        ("engine", "engine.put"), ("engine", "engine.dispatch"),
        ("engine", "engine.fetch")]
    assert len(traced.recorder().traces()) == 3
    put, dispatch, fetch = (m for _, _, m in got)
    assert put == {"bytes": img.nbytes, "words": 1}
    assert dispatch == {"backend": "jax", "op": "ychg", "px": img.size}
    # four int32 and one bool field a column, two int32 totals
    assert fetch["bytes"] == 20 * (4 * 4 + 1) + 2 * 4


def test_device_input_records_no_put_and_joins_current_trace(traced):
    x = jnp.zeros((2, 8, 16), jnp.uint8)
    tr = traced.Trace(process="tier")
    with traced.use_trace(tr):
        Engine().analyze_batch(x).to_host()
    assert [s[0] for s in tr.spans()] == ["engine.dispatch", "engine.fetch"]
    assert traced.recorder().traces() == []      # the tier finishes its own


def test_stream_and_pipeline_spans(traced, words_at_any_size):
    imgs = (np.random.default_rng(6).random((3, 8, 16)) < 0.5).astype(np.uint8)
    tr = traced.Trace(process="tier")
    with traced.use_trace(tr):
        list(Engine().analyze_stream([imgs[0], imgs[1:]]))
        Engine().run_pipeline(imgs, ["denoise", "ychg"])
    names = [(s[0], s[3].get("op")) for s in tr.spans()]
    assert names == [("engine.put", None), ("engine.dispatch", "ychg"),
                     ("engine.put", None), ("engine.dispatch", "ychg"),
                     ("engine.put", None), ("engine.dispatch", "denoise"),
                     ("engine.dispatch", "ychg")]
    # 16 columns of uint8: every put went as words
    assert [s[3]["words"] for s in tr.spans() if s[0] == "engine.put"] == [
        1, 1, 1]


@pytest.mark.parametrize("mask, words", [
    (np.ones((4, 8), np.uint8), 1), (np.ones((4, 8), np.int8), 1),
    (np.ones((4, 6), np.uint8), 0), (np.ones((4, 8), bool), 0),
    (np.ones((4, 8), np.float32), 0)])
def test_put_span_counts_word_shipping(traced, mask, words, monkeypatch):
    from repro.kernels import ingest

    monkeypatch.setattr(ingest, "MIN_BYTES", mask.nbytes)   # at the floor
    Engine().analyze(mask)
    (put,) = [m for _, n, m in _recorded(traced) if n == "engine.put"]
    assert put == {"bytes": mask.nbytes, "words": words}


def test_ccl_and_denoise_to_host_record_fetch(traced):
    img = (np.random.default_rng(7).random((8, 8)) < 0.5).astype(np.uint8)
    for op in ("ccl", "denoise"):
        Engine(op=op).analyze(img).to_host()
    fetches = [m for _, n, m in _recorded(traced) if n == "engine.fetch"]
    assert fetches == [{"bytes": 8 * 8 * 4 + 4}, {"bytes": 8 * 8 * 4}]


def test_dispatch_span_and_histogram_share_clock_reads(traced, monkeypatch):
    seen = []
    note = registry.note_dispatch

    def spy(name, seconds, op="ychg"):
        seen.append(seconds)
        note(name, seconds, op)

    monkeypatch.setattr(registry, "note_dispatch", spy)
    tr = traced.Trace()
    with traced.use_trace(tr):
        Engine().analyze_batch(np.ones((2, 4, 8), np.uint8))
    (span,) = [s for s in tr.spans() if s[0] == "engine.dispatch"]
    assert seen == [span[2] - span[1]]


def test_engine_records_nothing_with_tracing_off(traced, monkeypatch):
    from repro.obs import trace as trace_mod

    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

    monkeypatch.setattr(trace_mod, "_ANNOTATION", Annotation)
    traced.configure(enabled=False)
    Engine().analyze(np.ones((4, 8), np.uint8)).to_host()
    assert traced.recorder().traces() == [] and opened == []
