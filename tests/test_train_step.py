"""Compiled train step: chunked-scan parity, sharded execution, donation,
and the HBM/fragmentation probe plumbing (ISSUE 10 tentpole)."""

import dataclasses
import functools
import math

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


# The residual stream under `tp`: its rows lie split over the axis between
# a row-parallel matmul and the next column-parallel one, and the chunks
# go round the ring beside the matmuls (parallel/collective_matmul.py).
_RING_CASES = {
    # The four-chip cell's own mesh, its remat policy and chunked scan.
    "fsdp2-tp2-dots-chunk2": ({"dp": 1, "fsdp": 2, "tp": 2}, "dots", 2, 4),
    "fsdp2-tp2-full-scan": ({"dp": 1, "fsdp": 2, "tp": 2}, "full", 0, 4),
    "dp2-fsdp2-tp2-mlp": ({"dp": 2, "fsdp": 2, "tp": 2}, "mlp", 1, 4),
    # A ring of four: three hops a matmul, 32 rows in chunks of 8.
    "tp4-dots-unrolled": ({"dp": 1, "fsdp": 1, "tp": 4}, "dots", 4, 4),
}


@pytest.mark.parametrize("axes,policy,chunk,kv_heads",
                         list(_RING_CASES.values()), ids=list(_RING_CASES))
def test_step_with_the_residual_over_tp_matches_the_unsharded_step(
        axes, policy, chunk, kv_heads):
    """Under a mesh whose ``tp`` ring runs, the loss AND every gradient
    leaf, in float32, are the unsharded step's: the ring's sums are the
    all-reduce's sums, and a weight's gradient is one contraction over
    all the rows."""
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.collective_matmul import ring_size
    from ray_tpu.parallel.sharding import named_sharding, shard_pytree

    if len(jax.devices()) < axes["dp"] * axes["fsdp"] * axes["tp"]:
        pytest.skip("needs 8 virtual devices")
    cfg = _tiny(depth=4, scan_layers=True, scan_chunk=chunk, loss_chunk=8,
                remat_policy=policy, num_kv_heads=kv_heads)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(6).randint(0, 256, (8, 33)))
    ref_loss, ref_grads = _loss_and_grads(cfg, params, tokens)

    mesh = make_mesh(**axes)
    assert ring_size(mesh, 32) == axes["tp"]
    params = shard_pytree(params, mesh, param_logical_axes(cfg))
    tokens = jax.device_put(tokens, named_sharding(mesh, ("batch", "seq")))
    step = jax.jit(jax.value_and_grad(
        lambda p: causal_lm_loss(p, tokens, cfg, mesh)))
    assert "collective-permute" in step.lower(params).compile().as_text()
    loss, grads = step(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for path, got in jax.tree_util.tree_leaves_with_path(grads):
        want = functools.reduce(lambda t, k: t[k.key], path, ref_grads)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))


def test_expert_layers_train_under_the_ring_as_without_a_mesh():
    """A layer with a ``router`` takes the ring for its attention half
    and whole rows for its experts (``ffn`` asks for them): loss and
    gradients under ``fsdp=2 x tp=2`` are the unsharded ones."""
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import named_sharding, shard_pytree

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    cfg = dataclasses.replace(LlamaConfig.tiny(moe=True), num_layers=2,
                              num_kv_heads=4, loss_chunk=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(7).randint(0, 256, (4, 33)))
    ref_loss, ref_grads = _loss_and_grads(cfg, params, tokens)
    mesh = make_mesh(dp=1, fsdp=2, tp=2)
    params = shard_pytree(params, mesh, param_logical_axes(cfg))
    tokens = jax.device_put(tokens, named_sharding(mesh, ("batch", "seq")))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: causal_lm_loss(p, tokens, cfg, mesh)))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for name in ("wq", "wo", "router", "w_up", "w_down"):
        np.testing.assert_allclose(
            np.asarray(grads["layers"][name]),
            np.asarray(ref_grads["layers"][name]), rtol=5e-4, atol=5e-6,
            err_msg=name)


# (mesh axes or None, rows): where the ring must not run.
_NO_RING = {
    "no-mesh": (None, 32),
    "one-device": ({"dp": 1, "fsdp": 1, "tp": 1}, 32),
    "fsdp-only": ({"dp": 1, "fsdp": 4, "tp": 1}, 32),
    "dp-fsdp": ({"dp": 2, "fsdp": 2, "tp": 1}, 32),
    # Ring attention has the sequence: its layout is left as it was.
    "sp-tp": ({"dp": 1, "sp": 2, "tp": 2}, 32),
    # 33 rows do not divide by two.
    "tp-odd-rows": ({"dp": 1, "fsdp": 1, "tp": 2}, 33),
}


@pytest.mark.parametrize("axes,rows", list(_NO_RING.values()),
                         ids=list(_NO_RING))
def test_the_ring_is_absent_where_tp_has_no_rows_to_split(axes, rows):
    """No ``tp`` axis (or none of size over 1), an ``sp`` axis, or rows
    that do not divide: ``ring_size`` is 1 and the model traces no
    island (no ``shard_map`` and no ``ppermute`` but ring attention's
    own). Where the mesh has no ``tp`` to split over, the rule's axis is
    pruned and the residual's spec is the one it had before there was a
    ring. With no mesh at all a constraint is the identity and the step
    traces none."""
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.collective_matmul import ring_size
    from ray_tpu.parallel.sharding import (
        logical_to_spec, prune_spec, with_logical_constraint,
    )

    cfg = _tiny(depth=2, scan_layers=True, scan_chunk=2, loss_chunk=8,
                remat_policy="dots", num_kv_heads=4, use_flash=False)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((8, rows + 1), jnp.int32)
    mesh, ring_attention = None, False
    if axes is None:
        x = jnp.ones((2, 4, 8))
        assert with_logical_constraint(
            x, ("batch", "seq_tp", "embed"), mesh=None) is x
    else:
        size = math.prod(axes.values())
        if len(jax.devices()) < size:
            pytest.skip("needs 8 virtual devices")
        mesh = make_mesh(devices=jax.devices()[:size], **axes)
        ring_attention = axes.get("sp", 1) > 1
        if axes["tp"] == 1:
            assert (prune_spec(mesh, logical_to_spec(
                        ("batch", "seq_tp", "embed")))
                    == prune_spec(mesh, logical_to_spec(
                        ("batch", "seq", "embed"))))
    assert ring_size(mesh, rows) == 1
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p, t: causal_lm_loss(p, t, cfg, mesh)))(params, tokens))
    assert ring_attention or "ppermute" not in jaxpr
    assert ring_attention or "shard_map" not in jaxpr
    assert ("sharding_constraint" in jaxpr) is (mesh is not None)


# ------------------------------------- what "dots" keeps of the flash kernel

@pytest.fixture
def flash_interpreted(monkeypatch):
    """``causal_attention`` takes the Pallas flash kernels, interpreted
    (on the CPU ``flash_attention`` would take the einsum)."""
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))


def _flash_cfg(**kw):
    """Two layers in one chunk, as the train cells' chunk of four; 128
    tokens are one block of the kernels."""
    return _tiny(depth=2, **{"scan_layers": True, "scan_chunk": 2,
                             "use_flash": True, **kw})


def _flash_tokens():
    return jnp.asarray(np.random.RandomState(7).randint(0, 256, (2, 129)))


@pytest.mark.parametrize("policy", ["dots", "mlp", "full"])
@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_flash_gradients_are_the_unrematerialised_ones_bit_for_bit(
        flash_interpreted, policy, kv_heads):
    """What a remat policy keeps of the flash forward (``"dots"``: the
    named ``o3`` and ``lse``) or makes again (``"mlp"``, ``"full"``: the
    kernel runs a second time) is the same numbers: the loss and every
    gradient leaf of a two-layer chunk equal those of ``remat=False``,
    where nothing is made twice, to the bit."""
    cfg = _flash_cfg(remat_policy=policy, num_kv_heads=kv_heads)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = _flash_tokens()
    loss, grads = _loss_and_grads(cfg, params, tokens)
    want_loss, want = _loss_and_grads(
        dataclasses.replace(cfg, remat=False), params, tokens)
    assert float(loss) == float(want_loss)
    jax.tree.map(np.testing.assert_array_equal, grads, want)


@pytest.mark.parametrize("scan", [True, False], ids=["chunk", "unrolled"])
def test_dots_keeps_the_flash_forwards_o_and_lse_and_no_second_out(
        flash_interpreted, scan):
    """The residuals ``jax.checkpoint`` keeps under ``"dots"``, a layer:
    the kernel's ``o3`` ``[B*H, S, D]`` in the model's dtype and ``lse``
    ``[B*H, 1, S]`` float32, which ``ops/flash_attention.py:_core_fwd``
    names, and of ``out``'s shape ``[B, S, H, D]`` q alone: ``out`` is a
    re-laying of the kept ``o3``, not a residual of its own. Under
    ``"mlp"`` neither is kept (the backward runs the kernel again).
    The chunked scan stacks a layer's residuals behind one more axis and
    says only "output of scan"; the unrolled loop says where each is made.

    Fails on the tree before PR 74: ``"dots"`` keeps neither."""
    from jax._src.ad_checkpoint import saved_residuals

    from ray_tpu.models.llama import hidden_forward

    cfg = _flash_cfg(remat_policy="dots", scan_layers=scan)
    B, S, H, D = 2, 128, cfg.num_heads, cfg.dh
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = _flash_tokens()[:, :S]

    def kept(cfg):
        found = saved_residuals(
            lambda p: hidden_forward(p, tokens, cfg)[0].sum(), params)
        lead = (1,) if cfg.scan_layers else ()
        return [(aval.shape[len(lead):], aval.dtype, why)
                for aval, why in found if aval.shape[:len(lead)] == lead]

    def count(found, shape):
        return sum(1 for got, dtype, _ in found
                   if got == shape and dtype == jnp.float32)

    found = kept(cfg)
    layers = cfg.num_layers
    assert count(found, (B * H, S, D)) == layers          # o3
    assert count(found, (B * H, 1, S)) == layers          # lse
    assert count(found, (B, S, H, D)) == layers           # q, and no out
    if not scan:
        # Both come out of ``_core_fwd``'s one jitted call, which also
        # re-lays ``out``: no other equation of the forward reads them,
        # so jax hands neither on through a ``reduce_precision`` (a pass
        # of its own behind a custom call: 1.6 ms of the one-chip cell's
        # step when ``out`` was made from ``o3`` outside the call).
        from_kernel = [(got, why) for got, _, why in found
                       if "flash_attention.py" in why]
        assert sorted(got for got, _ in from_kernel) == sorted(
            [(B * H, S, D), (B * H, 1, S)] * layers)
        assert all("jitted function 'forward'" in why
                   for _, why in from_kernel), from_kernel
    other = kept(dataclasses.replace(cfg, remat_policy="mlp"))
    assert count(other, (B * H, S, D)) == 0
    assert count(other, (B * H, 1, S)) == 0


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
