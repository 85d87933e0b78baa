"""Compiled train step: chunked-scan parity, sharded execution, donation,
and the HBM/fragmentation probe plumbing (ISSUE 10 tentpole)."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, causal_lm_loss, init_params  # noqa: E402
from ray_tpu.models.llama import scan_chunks  # noqa: E402
from ray_tpu.train.compiled_step import CompiledTrainStep  # noqa: E402


def _tiny(depth=4, **kw):
    return dataclasses.replace(LlamaConfig.tiny(), num_layers=depth, **kw)


def _loss_and_grads(cfg, params, tokens):
    return jax.jit(
        jax.value_and_grad(lambda p: causal_lm_loss(p, tokens, cfg))
    )(params)


# ------------------------------------------------------------- parity

@pytest.mark.slow
def test_scan_chunk_parity_loss_and_grads():
    """Every scan schedule (classic K=1, chunked K=2, degenerate K=L) and
    the unrolled loop compute bitwise-close loss AND grads: the chunk
    schedule is a memory layout choice, not a numerics choice."""
    base = _tiny(depth=4)
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (2, 33))
    )
    ref_loss, ref_grads = _loss_and_grads(
        dataclasses.replace(base, scan_layers=False), params, tokens
    )
    for kw in (
        {"scan_layers": True, "scan_chunk": 0},
        {"scan_layers": True, "scan_chunk": 1},
        {"scan_layers": True, "scan_chunk": 2},
        {"scan_layers": True, "scan_chunk": 4},
        {"scan_layers": True, "scan_chunk": 2, "remat_policy": "mlp"},
        {"scan_layers": True, "scan_chunk": 2, "remat": False},
    ):
        cfg = dataclasses.replace(base, **kw)
        loss, grads = _loss_and_grads(cfg, params, tokens)
        np.testing.assert_allclose(
            float(loss), float(ref_loss), rtol=1e-6, err_msg=str(kw)
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6,
                err_msg=str(kw),
            ),
            grads, ref_grads,
        )


def test_scan_chunk_validation():
    cfg = _tiny(depth=4, scan_layers=True, scan_chunk=3)
    with pytest.raises(ValueError, match="must divide"):
        scan_chunks(cfg)
    params = init_params(_tiny(depth=4), jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 9), dtype=jnp.int32)
    with pytest.raises(ValueError, match="must divide"):
        causal_lm_loss(params, tokens, cfg)
    assert scan_chunks(_tiny(depth=6, scan_chunk=3)) == (3, 2)
    assert scan_chunks(_tiny(depth=4, scan_chunk=0)) == (1, 4)


# ------------------------------------------------- compiled step (CPU)

def test_compiled_step_smoke_and_compile_cache():
    """2-layer chunk=1 compiled step: one program, donated state, loss
    finite, no recompile on steady same-shape steps."""
    cfg = _tiny(depth=2, scan_layers=True, scan_chunk=1)
    step = CompiledTrainStep(cfg)
    params, opt_state = step.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, 256, (2, 17))
    )
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    # Training on one repeated batch must make progress (the optimizer
    # update really applied to the donated buffers).
    assert losses[-1] < losses[0]
    stats = step.compile_stats()
    assert stats["fn"] == "train_step"
    if stats.get("executables") is not None:
        assert stats["executables"] == 1
    assert step.num_params(params) > 0


def test_profiled_run_groups_steps_and_names_the_input_waits(tmp_path):
    """Under a profiler trace each call is a ``train_step`` step
    annotation carrying its step number (ROADMAP D7: the timeline span
    times the dispatch; the device's time is the profiler's), and the
    input iterator's wait and put are ``data.*`` annotations."""
    import glob
    import os

    import ray_tpu.data as rd

    cfg = _tiny(depth=2, scan_layers=True, scan_chunk=1)
    step = CompiledTrainStep(cfg)
    params, opt_state = step.init(jax.random.PRNGKey(0))
    rows = np.random.RandomState(1).randint(0, 256, (6, 17)).astype(np.int32)
    batches = rd.from_numpy(rows, column="tokens").iter_jax_batches(
        batch_size=2)
    first = next(batches)["tokens"]
    params, opt_state, _ = step(params, opt_state, first)  # compiles
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for batch in batches:
            params, opt_state, loss = step(params, opt_state,
                                           batch["tokens"])
        float(loss)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [(event.name, dict(event.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for event in line.events]
    steps = [s["step_num"] for name, s in events if name == "train_step"]
    assert steps == [1, 2]
    names = [name for name, _ in events]
    # The iterator is one batch ahead: two waits are left (the last finds
    # the end) and one put.
    assert names.count("data.next_batch") == 2
    assert names.count("data.device_put") == 1


@pytest.mark.slow
def test_compiled_step_donation_off():
    cfg = _tiny(depth=2, scan_layers=True, scan_chunk=2)
    step = CompiledTrainStep(cfg, donate=False)
    params, opt_state = step.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 9), dtype=jnp.int32)
    params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
    assert step.token_sharding() is None


@pytest.mark.slow
def test_compiled_step_chunked_matches_unrolled_training():
    """Three steps of chunked-scan training == three steps of unrolled
    training from the same init (the whole fused program is schedule-
    invariant, not just the forward)."""
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, 256, (2, 21))
    )
    losses = {}
    for name, kw in (
        ("unrolled", {"scan_layers": False}),
        ("chunked", {"scan_layers": True, "scan_chunk": 2}),
    ):
        cfg = _tiny(depth=4, **kw)
        step = CompiledTrainStep(cfg)
        params, opt_state = step.init(jax.random.PRNGKey(3))
        out = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
            out.append(float(loss))
        losses[name] = out
    np.testing.assert_allclose(
        losses["chunked"], losses["unrolled"], rtol=2e-5
    )


# ----------------------------------------------------- sharded (mesh)

@pytest.mark.slow
def test_compiled_step_sharded_matches_single_device():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from ray_tpu.parallel import make_mesh

    cfg = _tiny(depth=4, scan_layers=True, scan_chunk=2)
    tokens = jnp.asarray(
        np.random.RandomState(4).randint(0, 256, (4, 33))
    )

    ref = CompiledTrainStep(cfg)
    p, o = ref.init(jax.random.PRNGKey(0))
    ref_losses = []
    for _ in range(2):
        p, o, loss = ref(p, o, tokens)
        ref_losses.append(float(loss))

    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    step = CompiledTrainStep(cfg, mesh=mesh)
    sp, so = step.init(jax.random.PRNGKey(0))
    # The compiled init is sharding-invariant (threefry_partitionable):
    # same seed -> same model on any mesh.
    ref_embed = jax.device_get(ref.init(jax.random.PRNGKey(0))[0]["embed"])
    np.testing.assert_allclose(
        np.asarray(jax.device_get(sp["embed"])), np.asarray(ref_embed),
        rtol=1e-6,
    )
    tok = jax.device_put(tokens, step.token_sharding())
    got = []
    for _ in range(2):
        sp, so, loss = step(sp, so, tok)
        got.append(float(loss))
    np.testing.assert_allclose(got, ref_losses, rtol=1e-4)
    # Optimizer state (adam m/v) carries the SAME shardings as params —
    # the donation contract needs matching layouts on both sides.
    mu = so[0].mu
    assert (mu["layers"]["wq"].sharding
            == sp["layers"]["wq"].sharding)


# The chunked loss under a mesh: lm_head is asked for whole along `embed`
# once, before the loss's scans (models/llama.py:causal_lm_loss).
_LOSS_MESHES = {
    "fsdp2-tp2": {"dp": 1, "fsdp": 2, "tp": 2},
    "dp2-fsdp2-tp2": {"dp": 2, "fsdp": 2, "tp": 2},
    "tp2": {"dp": 1, "fsdp": 1, "tp": 2},
    "no-mesh": None,
}


@pytest.mark.parametrize("seqlen", [33, 29], ids=["whole-chunks", "padded-chunk"])
@pytest.mark.parametrize("axes", list(_LOSS_MESHES.values()),
                         ids=list(_LOSS_MESHES))
def test_chunked_loss_under_a_mesh_matches_the_plain_loss(axes, seqlen):
    """``_chunked_nll_sum`` ENGAGED under a mesh (32 or 28 targets over a
    ``loss_chunk`` of 8; the second pads its last chunk): the loss and
    ``lm_head``'s gradient, in float32, are those of the unchunked loss
    on one device, whether the head is gathered over ``fsdp`` first
    (``fsdp=2``), lies whole along ``embed`` already (``tp`` alone) or
    there is no mesh."""
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import named_sharding, shard_pytree

    if axes and len(jax.devices()) < axes["dp"] * axes["fsdp"] * axes["tp"]:
        pytest.skip("needs 8 virtual devices")
    cfg = _tiny(depth=2, scan_layers=True, loss_chunk=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(5).randint(0, 256, (4, seqlen)))
    ref_loss, ref_grads = _loss_and_grads(
        dataclasses.replace(cfg, loss_chunk=0), params, tokens)

    mesh = make_mesh(**axes) if axes else None
    if mesh is not None:
        params = shard_pytree(params, mesh, param_logical_axes(cfg))
        tokens = jax.device_put(
            tokens, named_sharding(mesh, ("batch", "seq")))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: causal_lm_loss(p, tokens, cfg, mesh)))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    assert grads["lm_head"].dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(grads["lm_head"]), np.asarray(ref_grads["lm_head"]),
        rtol=2e-5, atol=1e-6)


def test_chunked_loss_asks_for_nothing_where_the_head_lies_whole():
    """Without a mesh the chunked loss traces no sharding constraint (the
    one-chip step is the program it was before the head was placed by
    hand), and under ``tp`` alone the layout asked for is the one
    ``lm_head`` already has."""
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import named_sharding

    cfg = _tiny(depth=2, scan_layers=True, loss_chunk=8)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((4, 33), jnp.int32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p, t: causal_lm_loss(p, t, cfg)))(params, tokens))
    assert "while" in jaxpr or "scan" in jaxpr      # the chunked branch
    assert "sharding_constraint" not in jaxpr
    if len(jax.devices()) >= 4:
        tp = make_mesh(dp=1, fsdp=1, tp=2)
        assert (named_sharding(tp, (None, "vocab"))
                == named_sharding(tp, ("embed", "vocab")))
        sharded = make_mesh(dp=1, fsdp=2, tp=2)
        assert (named_sharding(sharded, (None, "vocab"))
                != named_sharding(sharded, ("embed", "vocab")))


# ------------------------------------------------------- HBM probe

def test_fragmentation_from_stats_preference_order():
    from ray_tpu.util.device_metrics import fragmentation_from_stats

    # peak pair preferred
    assert fragmentation_from_stats({
        "peak_bytes_in_use": 60, "peak_bytes_reserved": 100,
        "bytes_in_use": 10, "bytes_reserved": 10,
    }) == pytest.approx(0.4)
    # instantaneous pair next
    assert fragmentation_from_stats({
        "bytes_in_use": 75, "bytes_reserved": 100,
    }) == pytest.approx(0.25)
    # largest-free-block shatter estimate last
    assert fragmentation_from_stats({
        "bytes_in_use": 40, "bytes_limit": 100,
        "largest_free_block_bytes": 30,
    }) == pytest.approx(0.5)
    assert fragmentation_from_stats({}) is None


def test_hbm_snapshot_and_memory_metrics_declared():
    from ray_tpu.util import device_metrics

    snap = device_metrics.hbm_snapshot()
    assert isinstance(snap, dict)  # {} on CPU: no memory_stats
    # The fragmentation gauge is part of the declared metric surface.
    assert (device_metrics.MEMORY_FRAGMENTATION._name
            == "ray_tpu_device_memory_fragmentation_ratio")


def test_instrumented_jit_sample_memory_counts_compiles():
    from ray_tpu.util import device_metrics

    calls = {"n": 0}

    def f(x):
        calls["n"] += 1
        return x * 2

    wrapped = device_metrics.instrumented_jit(f, sample_memory=True)
    out = wrapped(jnp.asarray(3.0))
    assert float(out) == 6.0
    out = wrapped(jnp.asarray(4.0))
    assert float(out) == 8.0
    assert calls["n"] == 1  # traced once: same shape, no recompile
