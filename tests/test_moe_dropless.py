"""parallel/moe.py: dropless sorted/grouped dispatch against a per-token
Python-loop float32 reference. CPU, seeded, small."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.parallel.moe import moe_ffn  # noqa: E402

M, F = 16, 24
# (experts, experts a token, tokens): fewer tokens than experts; k * T
# no multiple of E; OLMoE's 64 top-8 at a decode batch; one expert only.
CASES = [(8, 2, 5), (4, 3, 7), (64, 8, 6), (4, 1, 16)]
IDS = ["T-lt-E", "kT-not-multiple-of-E", "64-top-8", "top-1"]


def _weights(E, seed):
    """(router [M, E], w_up, w_gate [E, M, F], w_down [E, F, M])."""
    rng = np.random.RandomState(seed)

    def normal(*shape, scale=0.3):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    return (normal(M, E, scale=1.0), normal(E, M, F), normal(E, M, F),
            normal(E, F, M))


def _tokens(T, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(1, T, M) * 0.7,
                       jnp.float32)


def _choices(x, router, k):
    """Each token's top-k experts, by NumPy."""
    logits = np.asarray(x, np.float64).reshape(-1, M) @ np.asarray(
        router, np.float64)
    return np.argsort(-logits, axis=-1, kind="stable")[:, :k]


def _per_token(x, router, w_up, w_gate, w_down, choices, mask=None):
    """For each token, for each expert it chose: its softmax gate (not
    renormalised) times down(silu(gate(x)) * up(x)). A Python loop over
    tokens in float32 jnp, so it differentiates."""
    xt = x.reshape(-1, M)
    probs = jax.nn.softmax(xt @ router, axis=-1)
    rows = []
    for t in range(xt.shape[0]):
        total = jnp.zeros((M,), jnp.float32)
        if mask is None or mask[t]:
            for e in choices[t]:
                h = jax.nn.silu(xt[t] @ w_gate[e]) * (xt[t] @ w_up[e])
                total = total + probs[t, e] * (h @ w_down[e])
        rows.append(total)
    return jnp.stack(rows).reshape(x.shape)


@pytest.mark.parametrize("E,k,T", CASES, ids=IDS)
def test_values_equal_the_per_token_loop(E, k, T):
    x = _tokens(T, 1)
    router, w_up, w_gate, w_down = _weights(E, 2)
    out, aux, load = jax.jit(lambda *a: moe_ffn(
        a[0], a[1], a[2], a[4], k=k, w_gate=a[3]))(
            x, router, w_up, w_gate, w_down)
    choices = _choices(x, router, k)
    expected = _per_token(x, router, w_up, w_gate, w_down, choices)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-6, rtol=1e-5)
    # The load counter is a NumPy count of the choices: nothing dropped.
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(choices.ravel(), minlength=E))
    assert int(load.sum()) == k * T and load.dtype == jnp.int32
    # Switch load-balancing term: E * sum_e share_e * mean prob_e.
    probs = np.asarray(jax.nn.softmax(x.reshape(-1, M) @ router, -1))
    share = np.bincount(choices.ravel(), minlength=E) / (k * T)
    assert float(aux) == pytest.approx(E * (share * probs.mean(0)).sum(),
                                       rel=1e-5)


@pytest.mark.parametrize("E,k,T", CASES, ids=IDS)
def test_gradients_equal_the_per_token_loops(E, k, T):
    x = _tokens(T, 3)
    weights = _weights(E, 4)
    choices = _choices(x, weights[0], k)

    def ours(x, router, w_up, w_gate, w_down):
        out, aux, _ = moe_ffn(x, router, w_up, w_down, k=k, w_gate=w_gate)
        return (out ** 2).sum() + 0.01 * aux

    def theirs(x, router, w_up, w_gate, w_down):
        out = _per_token(x, router, w_up, w_gate, w_down, choices)
        probs = jax.nn.softmax(x.reshape(-1, M) @ router, -1)
        share = np.bincount(choices.ravel(), minlength=E) / (k * T)
        return (out ** 2).sum() + 0.01 * E * (share * probs.mean(0)).sum()

    got = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3, 4)))(x, *weights)
    want = jax.grad(theirs, argnums=(0, 1, 2, 3, 4))(x, *weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=1e-4)


def test_all_tokens_on_one_expert():
    """One group holds every row and the other experts are empty."""
    E, k, T = 4, 1, 9
    router, w_up, w_gate, w_down = _weights(E, 5)
    x = jnp.abs(_tokens(T, 6))
    router = jnp.zeros((M, E)).at[:, 2].set(5.0)   # positive x: expert 2
    out, _, load = moe_ffn(x, router, w_up, w_down, k=k, w_gate=w_gate)
    assert np.asarray(load).tolist() == [0, 0, T, 0]
    expected = _per_token(x, router, w_up, w_gate, w_down,
                          np.full((T, 1), 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("E,k,T", CASES[:3], ids=IDS[:3])
def test_masked_rows_reach_no_expert_and_leave_the_rest_bit_equal(E, k, T):
    x = _tokens(T, 7)
    router, w_up, w_gate, w_down = _weights(E, 8)
    mask = np.ones(T, bool)
    mask[[0, T // 2]] = False
    run = jax.jit(lambda x, m: moe_ffn(x, router, w_up, w_down, k=k,
                                       w_gate=w_gate, token_mask=m))
    out, aux, load = run(x, jnp.asarray(mask)[None])
    full, _, full_load = run(x, jnp.ones((1, T), bool))
    out, full = np.asarray(out)[0], np.asarray(full)[0]
    assert (out[~mask] == 0).all()
    np.testing.assert_array_equal(out[mask], full[mask])
    choices = _choices(x, router, k)
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(choices[mask].ravel(), minlength=E))
    assert int(load.sum()) == k * mask.sum() < int(full_load.sum())
    assert np.isfinite(float(aux))
    # The masked rows' gradient is zero, the others' the unmasked run's.
    grad = jax.grad(lambda x, m: (run(x, m)[0] ** 2).sum())
    g = np.asarray(grad(x, jnp.asarray(mask)[None]))[0]
    g_full = np.asarray(grad(x, jnp.ones((1, T), bool)))[0]
    assert (g[~mask] == 0).all()
    np.testing.assert_allclose(g[mask], g_full[mask], atol=1e-6, rtol=1e-5)


def test_a_decode_step_reads_the_experts_of_its_live_slots_only():
    """32 slots of one token, 3 live: at most 3 * k experts are given a
    row, and an all-idle step gives none."""
    E, k, B = 64, 8, 32
    router, w_up, w_gate, w_down = _weights(E, 9)
    x = _tokens(B, 10).reshape(B, 1, M)
    active = np.zeros(B, bool)
    active[[1, 17, 30]] = True
    run = jax.jit(lambda a: moe_ffn(x, router, w_up, w_down, k=k,
                                    w_gate=w_gate, token_mask=a[:, None]))
    out, _, load = run(jnp.asarray(active))
    assert int(load.sum()) == 3 * k and int((load > 0).sum()) <= 3 * k
    assert (np.asarray(out)[~active] == 0).all()
    assert np.abs(np.asarray(out)[active]).min() > 0
    out, _, load = run(jnp.zeros(B, bool))
    assert int(load.sum()) == 0 and not np.asarray(out).any()


def test_ungated_experts_and_bfloat16():
    E, k, T = 8, 2, 12
    router, w_up, _, w_down = _weights(E, 11)
    x = _tokens(T, 12)
    out, _, _ = moe_ffn(x, router, w_up, w_down, k=k,
                        activation=jax.nn.gelu)
    probs = jax.nn.softmax(x.reshape(-1, M) @ router, -1)
    choices = _choices(x, router, k)
    want = np.stack([sum(
        np.asarray(probs[t, e] * (jax.nn.gelu(x[0, t] @ w_up[e]) @ w_down[e]))
        for e in choices[t]) for t in range(T)])
    np.testing.assert_allclose(np.asarray(out)[0], want, atol=2e-6, rtol=1e-5)
    half = [a.astype(jnp.bfloat16) for a in (x, w_up, w_down)]
    out16, _, load = moe_ffn(half[0], router, half[1], half[2], k=k,
                             activation=jax.nn.gelu)
    assert out16.dtype == jnp.bfloat16 and int(load.sum()) == k * T
    np.testing.assert_allclose(np.asarray(out16, np.float32)[0], want,
                               atol=0.05, rtol=0.05)


def test_nothing_has_a_capacity_axis():
    """No intermediate is larger than the k * T rows times the widest of
    hidden, expert width and experts: no [T, E, C] one-hot anywhere."""
    E, k, T = 64, 8, 128
    router, w_up, w_gate, w_down = _weights(E, 13)
    x = _tokens(T, 14)
    jaxpr = jax.make_jaxpr(lambda x: moe_ffn(
        x, router, w_up, w_down, k=k, w_gate=w_gate,
        token_mask=jnp.ones((1, T), bool)))(x)

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield int(np.prod(var.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) <= k * T * max(M, F, E)
    assert any(e.primitive.name.startswith("ragged_dot")
               for e in jaxpr.jaxpr.eqns)


# ---- the router's other arguments (PR 38) -----------------------------------

@pytest.mark.parametrize("E,k,T", CASES[:3], ids=IDS[:3])
def test_sigmoid_routing_with_a_selection_bias_equals_the_per_token_loop(
        E, k, T):
    """Sigmoid scores, the k experts of the largest score + bias, each
    gated by its score alone, renormalised and scaled: the same sorted
    grouped path as ever, against a per-token loop that selects and
    weights by NumPy. The bias is large enough to change who is chosen."""
    router, w_up, w_gate, w_down = _weights(E, 11)
    x = _tokens(T, 12)
    bias = jnp.asarray(np.random.RandomState(13).randn(E) * 0.5, jnp.float32)
    scale = 2.826
    out, _, expert_tokens = moe_ffn(
        x, router, w_up, w_down, k=k, w_gate=w_gate, score="sigmoid",
        select_bias=bias, renormalize=True, scale=scale)
    xt = np.asarray(x, np.float64).reshape(-1, M)
    scores = 1 / (1 + np.exp(-(xt @ np.asarray(router, np.float64))))
    chosen = np.argsort(-(scores + np.asarray(bias, np.float64)), axis=-1,
                        kind="stable")[:, :k]
    unbiased = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
    assert (np.sort(chosen) != np.sort(unbiased)).any()
    rows = []
    for t in range(T):
        gates = scores[t, chosen[t]]
        gates = gates / gates.sum() * scale
        total = np.zeros(M)
        for gate, e in zip(gates, chosen[t]):
            a = xt[t] @ np.asarray(w_gate[e], np.float64)
            h = a / (1 + np.exp(-a)) * (xt[t] @ np.asarray(w_up[e], np.float64))
            total += gate * (h @ np.asarray(w_down[e], np.float64))
        rows.append(total)
    np.testing.assert_allclose(np.asarray(out)[0], np.stack(rows),
                               atol=2e-5, rtol=2e-5)
    assert list(np.asarray(expert_tokens)) == list(
        np.bincount(chosen.reshape(-1), minlength=E))


def test_the_default_routing_is_the_program_it_was():
    """Softmax, no bias, gates as they fall, scale 1: naming the
    defaults traces the same program as not naming them, so OLMoE's
    compiled programs do not change."""
    router, w_up, w_gate, w_down = _weights(8, 1)
    x = _tokens(5, 2)

    def plain(x):
        return moe_ffn(x, router, w_up, w_down, k=2, w_gate=w_gate)

    def named(x):
        return moe_ffn(x, router, w_up, w_down, k=2, w_gate=w_gate,
                       score="softmax", select_bias=None, renormalize=False,
                       scale=1.0)

    assert str(jax.make_jaxpr(plain)(x)) == str(jax.make_jaxpr(named)(x))


# ---- a chip's share of the experts (PR 54) ----------------------------------

def _routing(E):
    rng = np.random.RandomState(E)
    return dict(score="sigmoid", renormalize=True, scale=2.5,
                select_bias=jnp.asarray(rng.randn(E) * 0.3, jnp.float32))


@pytest.mark.parametrize("E,k,T,chips", [(8, 2, 9, 2), (16, 4, 7, 4),
                                         (64, 8, 6, 16)],
                         ids=["2-chips", "4-chips", "16-chips-of-4"])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(E, k, T, chips):
    """``held=(first, E / n)`` on each of the n chips that share a
    layer, each with its own slice of the expert stacks and the router
    whole: the parts add up to what one chip holding every expert gives
    (so the shared expert, added once by the caller, is counted once),
    each chip's loads are its slice of the uncut layer's, and what it
    counts as gone elsewhere is everything it did not take. Rows of a
    mask reach no chip."""
    router, w_up, w_gate, w_down = _weights(E, 3)
    x = _tokens(T, 4)
    mask = jnp.asarray(np.arange(T) != 2)[None]
    routing = _routing(E)
    whole, _, load = moe_ffn(x, router, w_up, w_down, k=k, w_gate=w_gate,
                             token_mask=mask, **routing)
    count = E // chips
    total = jnp.zeros_like(whole)
    for chip in range(chips):
        here = slice(chip * count, (chip + 1) * count)
        part, _, part_load = moe_ffn(
            x, router, w_up[here], w_down[here], k=k, w_gate=w_gate[here],
            token_mask=mask, held=(chip * count, count), **routing)
        assert part_load.shape == (count + 1,)
        assert list(np.asarray(part_load[:count])) == list(
            np.asarray(load[here]))
        assert int(part_load[count]) == k * (T - 1) - int(load[here].sum())
        assert not np.asarray(part)[0, 2].any()
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-6, rtol=2e-6)
    # Read in place from all layers' stacks, as the serving programs do.
    stacked = [jnp.stack([jnp.zeros_like(w), w])[:, :count]
               for w in (w_up, w_down, w_gate)]
    part, _, _ = moe_ffn(x, router, stacked[0], stacked[1], k=k,
                         w_gate=stacked[2], token_mask=mask,
                         layer=jnp.int32(1), held=(0, count), **routing)
    first, _, _ = moe_ffn(x, router, w_up[:count], w_down[:count], k=k,
                          w_gate=w_gate[:count], token_mask=mask,
                          held=(0, count), **routing)
    np.testing.assert_array_equal(np.asarray(part), np.asarray(first))


def test_a_share_renormalises_over_every_expert_not_over_its_own():
    """The gates a chip applies are the uncut router's: a chip whose
    router knew its own experts alone would renormalise over them and
    give another layer."""
    E, k, T = 8, 2, 9
    router, w_up, w_gate, w_down = _weights(E, 3)
    x = _tokens(T, 4)
    routing = _routing(E)
    part, _, _ = moe_ffn(x, router, w_up[:4], w_down[:4], k=k,
                         w_gate=w_gate[:4], held=(0, 4), **routing)
    own = dict(routing, select_bias=routing["select_bias"][:4])
    alone, _, _ = moe_ffn(x, router[:, :4], w_up[:4], w_down[:4], k=k,
                          w_gate=w_gate[:4], **own)
    assert np.abs(np.asarray(part) - np.asarray(alone)).max() > 0.05


@pytest.mark.parametrize("name,want", [
    ("olmoe_tiny", "dbead347c9b4d8d6"), ("trinity_tiny", "a2b41b71ea20d1e8"),
    ("joyai_tiny", "35fe74be03783b18")])
def test_without_held_the_layer_is_bit_for_bit_what_it_was(name, want):
    """Digests taken at the parent commit (PR 53) of ``moe_ffn``'s three
    results on the second expert layer of each sparse fixture, read in
    place from the stack, under a mask, with the fixture's own routing."""
    import hashlib
    import json
    import os

    from benchmark import arch
    from ray_tpu.models import init_params

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", name, "config.json")) as f:
        cfg = arch.program_config(json.load(f))
    layers = init_params(cfg, jax.random.PRNGKey(7))["layers"]
    stack = next(s for s in (layers if isinstance(layers, tuple)
                             else (layers,)) if "router" in s)
    x = jnp.asarray(np.random.RandomState(5).randn(2, 9, cfg.hidden_size),
                    cfg.dtype)
    mask = jnp.asarray(np.random.RandomState(6).rand(2, 9) < 0.8)
    results = jax.jit(lambda x, stack, mask: moe_ffn(
        x, stack["router"][1], stack["w_up"], stack["w_down"], k=cfg.top_k,
        w_gate=stack["w_gate"], token_mask=mask, layer=jnp.int32(1),
        score=cfg.router_score,
        select_bias=stack.get("expert_bias", [None, None])[1],
        renormalize=cfg.route_norm, scale=cfg.route_scale))(x, stack, mask)
    digest = hashlib.sha256()
    for result in results:
        digest.update(np.asarray(result).tobytes())
    assert digest.hexdigest()[:16] == want


# ---- a route made elsewhere, and the gate's activation (PR 57) -------------

def _routed_elsewhere(h, x, router, w_up, w_gate, w_down, k, act, mask=None):
    """The per-token loop with the route read from ``h`` and the experts
    applied to ``x``: softmax gates (not renormalised) of h's top k."""
    ht, xt = h.reshape(-1, M), x.reshape(-1, M)
    probs = jax.nn.softmax(ht @ router, axis=-1)
    choices = _choices(h, router, k)
    rows = []
    for t in range(xt.shape[0]):
        total = jnp.zeros((M,), jnp.float32)
        if mask is None or mask[t]:
            for e in choices[t]:
                y = act(xt[t] @ w_gate[e]) * (xt[t] @ w_up[e])
                total = total + probs[t, e] * (y @ w_down[e])
        rows.append(total)
    return jnp.stack(rows).reshape(x.shape), choices


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("E,k,T", CASES[:3], ids=IDS[:3])
def test_a_route_made_elsewhere_multiplies_the_layers_own_input(E, k, T, act):
    """``dispatch(h)`` then ``moe_ffn(x, routed=)``: the experts each
    token of h chose multiply the same token of x, weighted by h's gates;
    the load is h's; SiLU or ReLU on the gate. Not the route of x."""
    from ray_tpu.parallel.moe import dispatch

    fn = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    h, x = _tokens(T, 5), _tokens(T, 6)
    router, w_up, w_gate, w_down = _weights(E, 7)
    mask = np.arange(T) != 1

    def layer(h, x, mask):
        routed = dispatch(h, router, k=k, token_mask=mask)
        return moe_ffn(x, None, w_up, w_down, k=k, w_gate=w_gate,
                       activation=fn, routed=routed)

    out, _, load = jax.jit(layer)(h, x, jnp.asarray(mask)[None])
    want, choices = _routed_elsewhere(h, x, router, w_up, w_gate, w_down, k,
                                      fn, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(choices[mask].ravel(), minlength=E))
    own, _, _ = moe_ffn(x, router, w_up, w_down, k=k, w_gate=w_gate,
                        activation=fn, token_mask=jnp.asarray(mask)[None])
    assert np.abs(np.asarray(own) - np.asarray(out)).max() > 1e-3


def test_a_route_made_elsewhere_reads_the_stack_in_place():
    """With ``layer`` the dispatch lays the groups at the layer's place
    in the stack, as ``moe_ffn`` does when it routes itself: the same
    numbers as that layer's own slice."""
    from ray_tpu.parallel.moe import dispatch

    E, k, T, L = 8, 2, 6, 3
    h, x = _tokens(T, 8), _tokens(T, 9)
    stacks = [_weights(E, 10 + i) for i in range(L)]
    router = stacks[1][0]
    w_up, w_gate, w_down = (jnp.stack([s[i] for s in stacks])
                            for i in (1, 2, 3))
    layer = jnp.asarray(1, jnp.int32)
    routed = dispatch(h, router, k=k, layer=layer, stack_layers=L)
    got, _, load = moe_ffn(x, None, w_up, w_down, k=k, w_gate=w_gate,
                           layer=layer, routed=routed)
    want, _, want_load = moe_ffn(
        x, None, w_up[1], w_down[1], k=k, w_gate=w_gate[1],
        routed=dispatch(h, router, k=k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))


def test_routing_its_own_input_gives_what_it_gave():
    """``moe_ffn`` without ``routed`` is ``moe_ffn(routed=dispatch(x))``:
    output, loss and load bit for bit, and no matmul or sort more."""
    from ray_tpu.parallel.moe import dispatch

    x = _tokens(7, 1)
    router, w_up, w_gate, w_down = _weights(8, 2)

    def own(x):
        return moe_ffn(x, router, w_up, w_down, k=2, w_gate=w_gate)

    def handed(x):
        return moe_ffn(x, None, w_up, w_down, k=2, w_gate=w_gate,
                       routed=dispatch(x, router, k=2))

    for a, b in zip(jax.jit(own)(x), jax.jit(handed)(x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    texts = [str(jax.make_jaxpr(f)(x)) for f in (own, handed)]
    for op in ("dot_general", "ragged_dot", "sort", "top_k"):
        assert texts[0].count(op) == texts[1].count(op)
