"""Pallas flash attention kernel tests (interpret mode on the CPU mesh;
the same kernel compiles for real on TPU)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention, mha_attention


def _rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             dtype=jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    B, S, H, D = 2, 256, 4, 64
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))
    ref = mha_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gqa():
    B, S, H, Hkv, D = 2, 256, 8, 2, 64
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)
    ref = mha_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_offsets():
    """Global-coordinate masking: a single query block at q_offset against
    a long KV prefix (the decode/ring-attention case)."""
    B, H, D = 1, 4, 64
    Skv, Sq, q_off = 512, 128, 384
    q = _rand((B, Sq, H, D), 0)
    k = _rand((B, Skv, H, D), 1)
    v = _rand((B, Skv, H, D), 2)
    ref = mha_attention(q, k, v, causal=True, q_offset=q_off)
    out = flash_attention(q, k, v, causal=True, q_offset=q_off,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match():
    B, S, H, D = 1, 128, 2, 32
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=True).sum()

    def loss_ref(q, k, v):
        return mha_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_flash_fallback_for_odd_shapes():
    # Non-tileable sequence length silently takes the XLA path.
    B, S, H, D = 1, 100, 2, 32
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = mha_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6)


def test_flash_gqa_gradients_match():
    """Backward with grouped KV heads: dK/dV must sum each group's query
    heads (reduced inside the grouped dkv kernel)."""
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_flash_decode_offset_gradients():
    """Fused backward with nonzero q_offset (the block-bound math must
    stay consistent with the forward's)."""
    B, S, Skv, H, D = 1, 128, 256, 2, 32
    q = _rand((B, S, H, D), 3)
    k = _rand((B, Skv, H, D), 4)
    v = _rand((B, Skv, H, D), 5)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, q_offset=128,
                               interpret=True).sum()

    def loss_ref(q, k, v):
        return mha_attention(q, k, v, causal=True, q_offset=128).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_flash_gqa_gradients_perhead_fallback(monkeypatch):
    """Shapes whose grouped [rep, Sq, D] Q/dO block would overflow VMEM
    use the per-query-head dkv kernel + external group sum; force that
    path and check grads still match XLA."""
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_dkv_grouped", lambda *shape: False)
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window", [1, 100, 128, 300, 4096])
def test_flash_window_matches_the_masked_einsum(window):
    """The forward kernel with a lower bound: token t attends to
    ``t - window < j <= t``, for windows under a block, of a block, over
    several and over the whole sequence, with GQA; against the XLA
    einsum with the same mask, which itself is checked by hand."""
    B, S, H, Hkv, D = 1, 512, 4, 2, 64
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, Hkv, D), 1)
    v = _rand((B, S, Hkv, D), 2)
    ref = mha_attention(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # The einsum's mask by hand, at one query row.
    t = 400
    lo = max(0, t + 1 - window)
    s = np.einsum("d,td->t", np.asarray(q)[0, t, 3],
                  np.asarray(k)[0, lo:t + 1, 1]) * D ** -0.5
    p = np.exp(s - s.max())
    want = (p / p.sum()) @ np.asarray(v)[0, lo:t + 1, 1]
    np.testing.assert_allclose(np.asarray(ref)[0, t, 3], want, atol=2e-5,
                               rtol=2e-5)


def test_flash_window_none_is_todays_kernel_and_a_window_has_no_grad():
    """``window=None`` traces the program it always did; under
    ``jax.grad`` a window raises by name instead of giving the causal
    kernels' gradient."""
    B, S, H, D = 1, 256, 2, 64
    q, k, v = (_rand((B, S, H, D), i) for i in range(3))

    def flash(window, **named):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True, **named,
            **({"window": window} if window != "unnamed" else {}))

    assert str(jax.make_jaxpr(flash("unnamed"))(q, k, v)) == \
        str(jax.make_jaxpr(flash(None))(q, k, v))
    np.testing.assert_array_equal(
        np.asarray(flash(None)(q, k, v)), np.asarray(flash("unnamed")(q, k, v)))
    with pytest.raises(NotImplementedError, match="no backward kernel"):
        jax.grad(lambda q: flash(64)(q, k, v).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        mha_attention(q, k, v, causal=False, window=64)


@pytest.mark.parametrize("S", [128, 512])
def test_flash_forward_takes_a_v_narrower_than_q_and_k(S):
    """Latent attention rebuilt: q and k 192 wide, v 128, the scale
    192 ** -0.5; the output is v's width. Against the XLA einsum. The
    backward kernels take one width: a gradient raises by name."""
    B, H = 1, 4
    q, k = _rand((B, S, H, 192), 0), _rand((B, S, H, 192), 1)
    v = _rand((B, S, H, 128), 2)
    ref = mha_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.shape == (B, S, H, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(NotImplementedError, match="narrower than q and k"):
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, interpret=True).sum())(q)


# -- PR 44: bfloat16 operands on the MXU, float32 accumulation -----------

fa = importlib.import_module("ray_tpu.ops.flash_attention")

# name: (Sq, Skv, H, Hkv, D, Dv, named blocks, q_offset, kv_offset,
# window, grouped dK/dV kernel)
_BF16_CASES = {
    "one_block": (128, 128, 2, 2, 128, 128, None, 0, 0, None, True),
    "s384_only_128_divides": (384, 384, 2, 2, 128, 128, None, 0, 0, None,
                              True),
    "block_q_256_block_k_128": (512, 512, 2, 2, 128, 128, (256, 128), 0, 0,
                                None, True),
    "block_q_128_block_k_256": (512, 512, 2, 2, 128, 128, (128, 256), 0, 0,
                                None, True),
    "offsets_move_the_diagonal": (256, 512, 2, 2, 128, 128, (128, 256),
                                  320, 64, None, True),
    "window_edge_inside_a_block": (512, 512, 4, 2, 128, 128, (256, 128), 0,
                                   0, 200, True),
    "widths_192_128": (256, 256, 2, 2, 192, 128, None, 0, 0, None, True),
    "gqa_grouped": (256, 256, 4, 2, 128, 128, None, 0, 0, None, True),
    "gqa_per_head_fallback": (256, 256, 4, 2, 128, 128, None, 0, 0, None,
                              False),
}
# dQ, dK and dV exist where the backward kernels do: no window, one width.
_BF16_PARAMS = [
    (name, out) for name, case in _BF16_CASES.items()
    for out in (("o",) if case[9] is not None or case[4] != case[5]
                else ("o", "dq", "dk", "dv"))
]


@functools.lru_cache(maxsize=None)
def _bf16_case(name):
    """(the kernel's, the float32 einsum's, the bfloat16 einsum's) o, dq,
    dk, dv on the same bfloat16 inputs, each as float32 arrays."""
    (Sq, Skv, H, Hkv, D, Dv, named, q_off, kv_off, window,
     grouped) = _BF16_CASES[name]
    bf16 = jnp.bfloat16
    q = _rand((1, Sq, H, D), 0).astype(bf16)
    k = _rand((1, Skv, Hkv, D), 1).astype(bf16)
    v = _rand((1, Skv, Hkv, Dv), 2).astype(bf16)
    w = _rand((1, Sq, H, Dv), 3)  # the loss weighs every output
    kw = dict(causal=True, q_offset=q_off, kv_offset=kv_off, window=window)
    named = {} if named is None else dict(block_q=named[0],
                                          block_k=named[1])

    def flash(q, k, v):
        return flash_attention(q, k, v, interpret=True, **named, **kw)

    def einsum(dtype):
        return lambda q, k, v: mha_attention(
            q.astype(dtype), k.astype(dtype), v.astype(dtype), **kw)

    dkv_grouped = fa._dkv_grouped
    if not grouped:
        fa._dkv_grouped = lambda *shape: False
    try:
        results = []
        for fn in (flash, einsum(jnp.float32), einsum(bf16)):
            out = {"o": fn(q, k, v)}
            if window is None and D == Dv:
                grads = jax.grad(
                    lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                     * w).sum(), argnums=(0, 1, 2))(q, k, v)
                out.update(zip(("dq", "dk", "dv"), grads))
            results.append({n: np.asarray(a.astype(jnp.float32))
                            for n, a in out.items()})
    finally:
        fa._dkv_grouped = dkv_grouped
    assert results[0]["o"].dtype == np.float32
    return results


@pytest.mark.parametrize("name,out", _BF16_PARAMS,
                         ids=[f"{n}-{o}" for n, o in _BF16_PARAMS])
def test_flash_bf16_operands_match_the_float32_einsum(name, out):
    """bfloat16 q, k, v (and dO) go to the matmuls as they are and are
    accumulated in float32: against the float32 einsum of the same
    bfloat16 inputs the kernel stays within bfloat16's tolerance, one
    rounding of the result (2**-8 of its size) and one of the
    probabilities, and is no further off than the repo's own bfloat16
    einsum, the path the same prefill takes at buckets under a block."""
    kernel, exact, einsum = (r[out] for r in _bf16_case(name))
    size = np.abs(exact).max()
    np.testing.assert_allclose(kernel, exact, atol=2 ** -6 * size, rtol=0)
    err = np.linalg.norm(kernel - exact) / np.linalg.norm(exact)
    einsum_err = np.linalg.norm(einsum - exact) / np.linalg.norm(exact)
    assert err < 2 ** -7, err
    assert err < 1.5 * einsum_err + 1e-4, (err, einsum_err)


def test_flash_float32_caller_is_computed_in_float32():
    """A float32 caller reads what it read: float32's tolerance against
    the einsum at the chosen blocks (256 here) with GQA and offsets,
    forward and all three gradients, and no bfloat16 anywhere in the
    traced program."""
    B, Sq, Skv, H, Hkv, D = 1, 256, 512, 4, 2, 64
    q, k, v = (_rand((B, Sq, H, D), 0), _rand((B, Skv, Hkv, D), 1),
               _rand((B, Skv, Hkv, D), 2))
    assert fa.choose_blocks(Sq, Skv, D, D, 2, 4).fwd == (256, 512)
    kw = dict(causal=True, q_offset=200, kv_offset=8)

    def flash(q, k, v):
        return flash_attention(q, k, v, interpret=True, **kw)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    ref = functools.partial(mha_attention, **kw)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)
    text = str(jax.make_jaxpr(jax.grad(loss(flash), argnums=(0, 1, 2)))(
        q, k, v))
    assert "bf16" not in text


def _pallas_calls(jaxpr):
    """Every pallas_call equation under ``jaxpr``, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("S,H,Hkv,D,Dv,window", [
    (2048, 32, 8, 128, 128, None),     # Mistral's prefill, training's S
    (8192, 32, 4, 128, 128, 2048),     # Trinity's window layers
    (4096, 32, 32, 192, 128, None),    # JoyAI: q and k 192, v 128
])
def test_flash_forward_call_writes_what_the_benchmark_reads(
        S, H, Hkv, D, Dv, window):
    """``benchmark/readers/window.py:FLASH`` knows a prefill's forward
    kernel by its outputs: ``pallas_<dtype>_<B·H>_<S>_<Dv>_f32_<B·H>_1_<S>``
    is o [B·H, S, Dv] in the input's dtype, then the float32 lse
    [B·H, 1, S], and nothing else. A third output, another order or a
    flat lse would silence ``prefill_flash_roofline.chat``."""
    from benchmark.readers.window import FLASH

    def arr(heads, width):
        return jax.ShapeDtypeStruct((1, S, heads, width), jnp.bfloat16)

    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=True))(
            arr(H, D), arr(Hkv, D), arr(Hkv, Dv))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    outs = [(v.aval.shape, str(v.aval.dtype)) for v in call.outvars]
    assert outs == [((H, S, Dv), "bfloat16"), ((H, 1, S), "float32")]
    # XLA names the custom call by its results: dtype, then dimensions.
    name = "pallas_" + "_".join(
        "_".join([{"bfloat16": "bf16", "float32": "f32"}[dtype],
                  *map(str, shape)]) for shape, dtype in outs)
    match = FLASH.match(name)
    assert match and int(match.group(2)) == S, name


def test_flash_block_choice_is_a_pure_function_of_the_shape():
    """The shape-to-block table of every caller there is: a later edit
    that sends a bucket to the einsum (None), or that lets the choice
    depend on anything but these arguments, fails here. Training is
    Mistral's and Nemo's b8 x 2048 (groups of 4: Q and dO of the four
    heads stay in VMEM, so dK/dV's grid block is the smaller); the
    buckets are every power of two a serving cell pads a prompt to."""
    choose = fa.choose_blocks
    assert choose(2048, 2048, 128, 128, 4, 2) == fa.Blocks(
        fwd=(512, 512), dq=(512, 512), dkv=(512, 256))
    assert fa._dkv_grouped(4, 2048, 128, 2)
    assert not fa._dkv_grouped(4, 4096, 128, 2)  # 16 MB of Q and dO
    for bucket in (128, 256, 512, 1024, 2048, 4096, 8192):
        for rep, d, dv in ((4, 128, 128),    # Mistral
                           (1, 128, 128),    # OLMoE
                           (8, 128, 128),    # Trinity
                           (1, 192, 128)):   # JoyAI
            got = choose(bucket, bucket, d, dv, rep, 2)
            # K and V at 192/128 of 8192 tokens are 12 MB of the 16.
            want = ((256, 512) if (bucket, d) == (8192, 192)
                    else (min(bucket, 512),) * 2)
            assert got is not None and got.fwd == want, (bucket, d, got)
    # Under a block, or where 128 does not divide: the einsum's.
    for s in (16, 32, 64, 100, 192):
        assert choose(s, s, 128, 128, 4, 2) is None
    # float32 callers and unequal lengths are tiled by the same rule.
    assert choose(256, 512, 64, 64, 2, 4).fwd == (256, 512)
    assert choose(384, 384, 128, 128, 1, 2).fwd == (128, 128)


# ---- the streamed forward: K and V a block at a time (PR 57) ---------------

def _streamed(q, k, v, *, block_q, block_k, window=None, q_offset=0,
              kv_offset=0):
    """``_flash_fwd_streamed`` on [B, S, H, D] arrays, as
    ``flash_attention`` calls it; (o [B, S, H, Dv], lse [B*H, 1, S])."""
    B, Sq, H, D = q.shape
    o3, lse = fa._flash_fwd_streamed(
        fa._to_heads3(q), fa._to_heads3(k), fa._to_heads3(v), heads=H,
        kv_heads=k.shape[2], scale=D ** -0.5, causal=True,
        q_offset=q_offset, kv_offset=kv_offset, block_q=block_q,
        block_k=block_k, interpret=True, window=window)
    return o3.reshape(B, H, Sq, v.shape[3]).transpose(0, 2, 1, 3), lse


@pytest.mark.parametrize("window", [None, 1, 100, 128, 300, 4096])
def test_streamed_forward_matches_the_masked_einsum(window):
    """The streamed form at small blocks, groups of 7 query heads a KV
    head: full and window masks, windows under a block, of a block and
    over several; equal to the XLA einsum, and its output and
    log-sum-exp bit for bit the resident form's at the same blocks."""
    B, S, H, Hkv, D = 1, 512, 7, 1, 64
    q, k, v = (_rand((B, S, H, D), 0), _rand((B, S, Hkv, D), 1),
               _rand((B, S, Hkv, D), 2))
    out, lse = _streamed(q, k, v, block_q=128, block_k=128, window=window)
    ref = mha_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    o3, lse_resident = fa._flash_fwd(
        fa._to_heads3(q), fa._to_heads3(k), fa._to_heads3(v), heads=H,
        kv_heads=Hkv, scale=D ** -0.5, causal=True, q_offset=0, kv_offset=0,
        block_q=128, block_k=128, interpret=True, window=window)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(o3.reshape(B, H, S, D).transpose(0, 2, 1, 3)))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_resident))


@pytest.mark.parametrize("blocks,window,q_offset,kv_offset", [
    ((128, 256), 300, 500, 0),     # a decode-like slice of queries
    ((256, 128), None, 128, 64),   # unequal blocks, both offsets
    ((128, 128), None, 0, 300),    # query blocks that attend to nothing
])
def test_streamed_forward_offsets_and_empty_blocks(blocks, window, q_offset,
                                                   kv_offset):
    """Global coordinates, as the resident form: offsets move the
    diagonal, and a query block before every key is written as zeros
    (its one visit multiplies nothing)."""
    B, Sq, Skv, H, Hkv, D = 1, 256, 768, 2, 2, 64
    q, k, v = (_rand((B, Sq, H, D), 0), _rand((B, Skv, Hkv, D), 1),
               _rand((B, Skv, Hkv, D), 2))
    out, _ = _streamed(q, k, v, block_q=blocks[0], block_k=blocks[1],
                       window=window, q_offset=q_offset, kv_offset=kv_offset)
    resident = flash_attention(
        q, k, v, causal=True, window=window, q_offset=q_offset,
        kv_offset=kv_offset, block_q=blocks[0], block_k=blocks[1],
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(resident))
    if kv_offset > q_offset + Sq:
        assert not np.asarray(out)[:, :kv_offset - q_offset].any()


def test_a_window_layers_query_block_fetches_only_its_windows_blocks():
    """The walk at the cell's shape, 16,384 x 16,384 in blocks of 512: a
    full layer's query block i visits key blocks 0 .. i (528 visits),
    a window-4096 layer's only those that t - 4096 < j <= t touches, nine
    at most (252 visits); first and last visits flagged once a query
    block, and only the blocks an edge crosses masked."""
    full = fa.stream_visits(16384, 16384, 512, 512, causal=True)
    assert len(full[0]) == 32 * 33 // 2
    q_blocks, k_blocks, flags = fa.stream_visits(
        16384, 16384, 512, 512, causal=True, window=4096)
    assert len(q_blocks) == sum(min(i, 8) + 1 for i in range(32)) == 252
    for i in range(32):
        mine = [(k, f) for q, k, f in zip(q_blocks, k_blocks, flags)
                if q == i]
        assert [k for k, _ in mine] == list(range(max(0, i - 8), i + 1))
        assert [bool(f & fa._FIRST) for _, f in mine] == \
            [True] + [False] * (len(mine) - 1)
        assert [bool(f & fa._LAST) for _, f in mine] == \
            [False] * (len(mine) - 1) + [True]
        # The diagonal block, and the one the window's edge crosses.
        masked = [k for k, f in mine if f & fa._MASKED]
        assert masked == ([i - 8, i] if i >= 8 else [i])
    # A window that is no whole number of blocks reaches one block more.
    assert len(fa.stream_visits(2048, 2048, 512, 512, causal=True,
                                window=600)[0]) == 1 + 2 + 3 + 3


def test_flash_streams_what_the_resident_form_cannot_hold():
    """``flash_attention`` itself, at a sequence whose K and V of a head
    are over the VMEM budget whole (float32, 8,192 keys of 128 + 128:
    16.8 MB double-buffered): it builds the streamed call, full and
    window, and a slice of queries at the sequence's end equals the
    einsum; one block under that length it builds the resident call."""
    from benchmark.readers.smallthinker import STREAMED
    from benchmark.readers.window import FLASH

    S, H, Hkv, D = 8192, 7, 1, 128
    assert fa.choose_blocks(S, S, D, D, 7, 4) == fa.Blocks(
        fwd=(512, 512), dq=(128, 128), dkv=(128, 128), streamed=True)
    assert not fa.choose_blocks(S // 2, S // 2, D, D, 7, 4).streamed
    assert fa.choose_blocks(16384, 16384, 128, 128, 7, 2) == fa.Blocks(
        fwd=(512, 512), dq=(128, 128), dkv=(128, 128), streamed=True)

    def name_of(s, window):
        def arr(heads):
            return jax.ShapeDtypeStruct((1, s, heads, D), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=True))(
                arr(H), arr(Hkv), arr(Hkv))
        (call,) = _pallas_calls(jaxpr.jaxpr)
        return "pallas_" + "_".join(
            "_".join(["f32", *map(str, v.aval.shape)]) for v in call.outvars)

    for window in (None, 4096):
        streamed, resident = name_of(S, window), name_of(S // 2, window)
        assert streamed == f"pallas_f32_{H}_1_{S}_f32_{H}_{S}_{D}"
        assert STREAMED.match(streamed) and not FLASH.match(streamed)
        assert FLASH.match(resident) and not STREAMED.match(resident)

    # The last 256 queries against all 8,192 keys: 17 visits of 512 keys.
    q = _rand((1, 256, H, D), 0)
    k, v = _rand((1, S, Hkv, D), 1), _rand((1, S, Hkv, D), 2)
    assert fa.choose_blocks(256, S, D, D, 7, 4).streamed
    for window in (None, 4096):
        out = flash_attention(q, k, v, causal=True, window=window,
                              q_offset=S - 256, interpret=True)
        ref = mha_attention(q, k, v, causal=True, window=window,
                            q_offset=S - 256)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
