"""Mixture-of-experts FFN: dropless, sorted, grouped.

Absent from the reference (SURVEY.md §2.5 — no EP/MoE in Ray). The
router (:func:`route`, float32) is the model's to state: scores by
softmax over all experts or by a sigmoid each, the top k chosen by score
plus an optional selection bias that is not in the gate, the chosen
gates renormalised to sum to one or left as they fall, and a scale. The
default is OLMoE's: softmax, no bias, ``norm_topk_prob=false``, scale 1.

Where the router reads is the model's to state too. :func:`dispatch` is
everything that depends on the router's input alone: the logits, the
top k, the sort into groups and the grouped matmul's walk.
:func:`moe_ffn` makes it from its own input, the FFN's normed one, as
OLMoE, Trinity, JoyAI and GLM route; or it is handed one made elsewhere
(``routed=``): SmallThinker's router reads the ATTENTION's normed input,
so ``llama.block`` dispatches before the attention and nothing of the
route waits for it (PR 57).

One path, for training, prefill and decode: the ``k * T`` (token, expert)
assignments are sorted by expert, the tokens' rows gathered in that
order, and the three expert matmuls run as grouped matmuls over
``group_sizes``, so every assignment is computed, none is dropped, and
nothing has a capacity axis. Rows of a ``token_mask`` (inactive decode
slots, a prefill bucket's padding) sort behind the last group and belong
to no expert. Experts are sharded over the "ep" mesh axis by their
weights' logical axis "expert"; GSPMD partitions the grouped matmuls (an
all-to-all layout is ROADMAP R3's). A chip that holds a share of a
layer's experts says which (``moe_ffn(held=)``): it routes over all of
them, computes its own and drops the rest, with no exchange.

What multiplies the groups is ops/grouped_matmul.py's to choose, from
what it can see and nothing a caller sets
(``grouped_matmul.grouped_path``): on a TPU, outside any mesh of more
than one device, a call of few rows a group (a decode step's 256 rows
over 64 to 256 experts, a prefill's shortest buckets) runs the Pallas
kernel sized to those rows, gate, up and the activation in one call and
down in a second; every other call (the CPU, a mesh, a prefill or a
training step of thousands of rows) runs ``jax.lax.ragged_dot`` three
times, operation for operation what it was. A gradient through the
kernel is ``ragged_dot``'s (a ``custom_vjp``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul
from .sharding import _current_mesh


def route(logits: jax.Array, k: int, *, score: str = "softmax",
          select_bias: Optional[jax.Array] = None,
          renormalize: bool = False, scale: float = 1.0):
    """(scores [T, E], gates [T, k], experts [T, k]) from float32 router
    logits [T, E]. ``score``: "softmax" over the experts or "sigmoid" of
    each. With ``select_bias`` [E] the k experts are those of the
    largest score + bias, and a gate is the score alone. ``renormalize``
    divides a token's k gates by their sum; ``scale`` multiplies them."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"router score {score!r}: softmax or sigmoid")
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if select_bias is None:
        gates, experts = jax.lax.top_k(scores, k)              # [T, k]
    else:
        _, experts = jax.lax.top_k(
            scores + select_bias.astype(jnp.float32), k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        gates = gates * scale
    return scores, gates, experts


class Routed(NamedTuple):
    """What :func:`dispatch` hands the expert layer: of ``T`` tokens'
    ``k * T`` assignments the gates [T, k] float32 (0 where an
    assignment reaches no expert here), their stable order by expert
    [k * T], the rows a group holds [E] int32, which sorted rows lie in
    a group at all ([k * T, 1] bool, or None where every one does), the
    Switch load-balancing loss, with ``held`` the assignments that went
    elsewhere (else None), and the grouped matmul over those groups
    (ops/grouped_matmul.py: its walk is made with it)."""
    gates: jax.Array
    order: jax.Array
    group_sizes: jax.Array
    in_a_group: Optional[jax.Array]
    aux: jax.Array
    elsewhere: Optional[jax.Array]
    matmul: Callable


def dispatch(
    x: jax.Array,           # [B, S, M]: what the router reads
    router_w: jax.Array,    # [M, E]
    *,
    k: int = 2,
    token_mask: Optional[jax.Array] = None,  # [B, S] 1=route, 0=ignore
    layer: Optional[jax.Array] = None,  # [] int32: the weights are stacks
    stack_layers: Optional[int] = None,  # of this many layers
    mesh=None,
    held: Optional[Tuple[int, int]] = None,
    **routing,              # route()'s: score, select_bias, renormalize, scale
) -> Routed:
    """The route of x's tokens, and everything of the expert layer that
    follows from it without the experts' own input: see :class:`Routed`
    and :func:`moe_ffn`, whose arguments these are."""
    B, S, M = x.shape
    E = router_w.shape[1]
    T = B * S
    elsewhere = None
    with jax.named_scope("moe.route"):
        router_logits = jnp.einsum(
            "tm,me->te", x.reshape(T, M).astype(jnp.float32),
            router_w.astype(jnp.float32))
        probs, gates, experts = route(router_logits, k, **routing)
        if held is not None:
            # From here on "expert" is an index into the held stack, E
            # their number, and ``E`` itself the expert that does not
            # exist (below), where assignments held elsewhere go too.
            first, count = held
            here = (experts >= first) & (experts < first + count)
            assigned = k * (T if token_mask is None
                            else token_mask.sum().astype(jnp.int32))
            experts = jnp.where(here, experts - first, count)
            gates = gates * here
            probs = probs[:, first:first + count]
            E = count
        chosen = jax.nn.one_hot(experts, E, dtype=jnp.float32).sum(axis=1)
        if token_mask is not None:
            live = token_mask.reshape(T).astype(bool)
            chosen = chosen * live[:, None]
            # Expert E does not exist: the masked rows sort last, behind
            # every group.
            experts = jnp.where(live[:, None], experts, E)
            gates = gates * live[:, None]
        expert_tokens = chosen.sum(axis=0)                     # [E]
        # Switch aux loss: E * sum_e f_e * p_e (share routed x mean prob).
        aux = E * jnp.sum(expert_tokens / (k * T) * probs.mean(axis=0))
        group_sizes = expert_tokens.astype(jnp.int32)
        order = jnp.argsort(experts.reshape(T * k))            # stable
        in_a_group = None
        if token_mask is not None or held is not None:
            in_a_group = (jnp.arange(T * k) < group_sizes.sum())[:, None]
        if held is not None:
            elsewhere = assigned - group_sizes.sum()
        groups = group_sizes
        if layer is not None:
            groups = jnp.zeros((stack_layers, E), jnp.int32).at[layer].set(
                group_sizes).reshape(stack_layers * E)
        matmul = grouped_matmul(T * k, groups, experts=E,
                                mesh=mesh or _current_mesh())
    return Routed(gates, order, group_sizes, in_a_group, aux, elsewhere,
                  matmul)


def moe_ffn(
    x: jax.Array,           # [B, S, M]
    router_w: jax.Array,    # [M, E]
    w_in: jax.Array,        # [E, M, F]
    w_out: jax.Array,       # [E, F, M]
    *,
    k: int = 2,
    w_gate: Optional[jax.Array] = None,  # [E, M, F] for gated (SwiGLU) experts
    activation=jax.nn.silu,
    token_mask: Optional[jax.Array] = None,  # [B, S] 1=route, 0=ignore
    layer: Optional[jax.Array] = None,  # [] int32: the weights are stacks
    mesh=None,              # the mesh the program is partitioned over
    held: Optional[Tuple[int, int]] = None,  # (first, count): None is all
    routed: Optional[Routed] = None,  # a dispatch made elsewhere
    **routing,              # route()'s: score, select_bias, renormalize, scale
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (output [B,S,M], Switch load-balancing loss, tokens
    assigned to each expert [E] int32). Masked rows give zero, reach no
    expert and are not counted.

    ``routed``: the :func:`dispatch` of these tokens where the router
    read something else than ``x`` (made by the caller with the same
    ``k``, ``token_mask``, ``layer``, ``held`` and routing; ``router_w``
    is then unread). None: the router reads ``x``.

    ``activation`` is the gate's (SiLU: SwiGLU; ``jax.nn.relu``: ReGLU),
    applied in float32 as the grouped matmul's epilogue.

    ``held`` = (first, count): this chip holds experts ``first ..
    first + count`` of the router's ``E = router_w.shape[1]``, one of
    ``E / count`` chips that share the layer, and the expert stacks hold
    those ``count`` alone. The router's logits, the top k, the
    renormalisation and the scale are over all E, as on every chip of
    the deployment; an assignment to an expert held elsewhere is dropped
    before the sort, reaches no group and adds nothing, so the output is
    this chip's part of the layer's routed sum (the parts of all the
    chips add up to the uncut layer's; nothing here stands in for the
    chips that are not there). The third result is then ``[count + 1]``:
    the held experts' tokens, and behind them the count of assignments
    that went elsewhere.

    With ``layer``, ``w_in``, ``w_gate`` and ``w_out`` are all layers'
    experts ``[L, E, ...]`` and this layer's are read where they lie, as
    groups ``layer * E ..`` of ``L * E`` with every other group empty. A
    layer scan that slices its layer's experts out first copies them
    (805 MB a layer at OLMoE's widths, a third of the decode step: chip
    run, PR 28): the grouped matmul is a custom call and cannot read a
    slice in place. The serving programs pass the stack; training
    slices, where a stack's gradient would be summed whole per layer.

    ``mesh``: the mesh the caller's program is partitioned over (``ffn``
    passes its own; else the context's, as ``with_logical_constraint``):
    one of the things ``grouped_path`` chooses the grouped matmul by."""
    B, S, M = x.shape
    T = B * S
    xt = x.reshape(T, M)
    if routed is None:
        routed = dispatch(
            x, router_w, k=k, token_mask=token_mask, layer=layer,
            stack_layers=None if layer is None else w_in.shape[0],
            mesh=mesh, held=held, **routing)
    gates, order, group_sizes, in_a_group = (
        routed.gates, routed.order, routed.group_sizes, routed.in_a_group)
    with jax.named_scope("moe.route"):
        rows = xt[order // k]                                  # [k*T, M]
        if in_a_group is not None:
            # Rows behind the last group: a grouped matmul leaves there
            # whatever the backend does (zeros on the CPU, not on the
            # TPU: chip run, PR 28), forward and backward, so they are
            # cut off on the way in and on the way out.
            rows = jnp.where(in_a_group, rows, 0)
    with jax.named_scope("moe.experts"):
        if layer is not None:
            w_in, w_out, w_gate = (
                None if w is None else w.reshape((-1,) + w.shape[2:])
                for w in (w_in, w_out, w_gate))

        def activate(h, g=None):
            if g is None:
                return activation(h)
            return activation(g.astype(jnp.float32)).astype(h.dtype) * h

        h = routed.matmul(
            rows, (w_in,) if w_gate is None else (w_in, w_gate), activate)
        y = routed.matmul(h, (w_out,))                         # [k*T, M]
        if in_a_group is not None:
            y = jnp.where(in_a_group, y, 0)
    with jax.named_scope("moe.combine"):
        # Back to token order (a gather by the inverse permutation, no
        # scatter-add), then the k results of a token weighted by its
        # gates and summed in float32.
        back = jnp.argsort(order)
        out = jnp.einsum("tkm,tk->tm", y[back].reshape(T, k, M), gates,
                         preferred_element_type=jnp.float32)
    if routed.elsewhere is not None:
        group_sizes = jnp.concatenate(
            [group_sizes, routed.elsewhere[None]])
    return out.astype(x.dtype).reshape(B, S, M), routed.aux, group_sizes
