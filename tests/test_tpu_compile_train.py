"""The four training programs at tiny widths, compiled for a described
v5e host (tests/tpu_rehearsal.py): what a mesh of ``fsdp x tp`` and one
device put into the step's text."""

import dataclasses
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
from tpu_rehearsal import (  # noqa: E402
    B, HLO_INSTRUCTION, PAGES_PER_SEQ, POOL_PAGES, decode_program,
    serve_shapes)


def _dense_cfg(vocab_size=512):
    """The Nemo cell's trainer settings at tiny widths."""
    return LlamaConfig(
        vocab_size=vocab_size, hidden_size=256, intermediate_size=512,
        num_layers=4, num_heads=2, num_kv_heads=2, head_dim=128,
        dtype=jnp.bfloat16, remat_policy="dots", scan_layers=True,
        scan_chunk=2, loss_chunk=256,
    )


def _dense_train_step(cfg, mesh, sharding=None):
    """The text of ``cfg``'s train step under ``mesh``, b4 x 512 tokens,
    traced anew; without a mesh (the one-chip cell's own path: no
    ``shard_map`` round the kernels) with every argument on ``sharding``."""
    from ray_tpu.train.compiled_step import CompiledTrainStep

    step = CompiledTrainStep(cfg, mesh=mesh, learning_rate=1e-5)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with jax.threefry_partitionable(True):
        state = jax.eval_shape(step._init, key)
        shardings = (jax.tree.map(lambda _: sharding, state) if mesh is None
                     else step._init.lower(key).compile().output_shardings)
    params, opt_state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, shardings)
    tokens = jax.ShapeDtypeStruct(
        (4, 513), jnp.int32, sharding=step.token_sharding() or sharding)
    train = step._step.__wrapped_jit__.lower(params, opt_state, tokens)
    return train.compile().as_text()


def _dense_programs(v5e, mesh):
    """The texts of a dense model's train step under ``mesh`` and of its
    decode step on one device, each traced anew."""
    cfg = _dense_cfg()
    serve = dataclasses.replace(cfg, remat_policy="none", scan_chunk=0)
    decode = decode_program(serve, v5e, *serve_shapes(
        serve, v5e, B, POOL_PAGES, PAGES_PER_SEQ))
    return _dense_train_step(cfg, mesh), decode.as_text()


@pytest.fixture(scope="module")
def either_way(v5e, v5e_host):
    """``(asked, texts)``: the dense programs' texts under each answer
    ``grouped_path`` could give, each traced anew with the kernels'
    dispatch steered to the TPU as ``as_tpu`` steers it, and what was
    asked of the rule meanwhile. Compiled once for the two cases that
    read the ``fsdp=2 x tp=2`` train step's text."""
    import importlib

    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh(devices=v5e_host, dp=1, fsdp=2, tp=2)
    asked, texts = [], []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(importlib.import_module("ray_tpu.ops.flash_attention"),
                      "_on_tpu", lambda: True)
        for answer in ("small_rows", "ragged_dot"):
            patch.setattr(
                gm, "grouped_path",
                lambda *a, answer=answer, **k: asked.append(a) or answer)
            texts.append(_dense_programs(v5e, mesh))
    return asked, texts


def test_programs_without_experts_are_the_same_either_way(either_way):
    """``train-nemo12b-4chip``, ``train-mistral7b-1chip``,
    ``serve-mistral7b-chat`` and ``serve-brumby-c16-8k`` run no expert
    layer, so nothing asks ops/grouped_matmul.py for a path: a dense
    model's train step under an ``fsdp=2 x tp=2`` mesh of a described
    v5e host, as the Nemo cell's, and its decode step compile to the
    same text whichever answer ``grouped_path`` would give. Those cells
    cannot tell a tree with the kernel from one without."""
    asked, texts = either_way
    assert not asked
    assert texts[0] == texts[1]
    train, decode = texts[0]
    assert "all-reduce" in train or "all-gather" in train
    assert "tpu_custom_call" in train             # the flash kernels
    assert "ragged" not in train + decode


_COLLECTIVE = re.compile(
    r" = \(?\w+\[([\d,]+)\]\S* "
    r"(all-gather|all-reduce|reduce-scatter|fusion)(?:-start)?\(")


def _head_collectives(text, shapes):
    """``(kind, in_a_while_body)`` of every collective in a compiled
    step's text whose result has one of ``shapes``: an all-gather, an
    all-reduce, a reduce-scatter, or a fusion that wraps one (XLA:TPU's
    ``all-reduce-scatter``, which carries no collective's name)."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))  # counted at the call
    found, computation = [], None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            computation = computation.lstrip("%")
            continue
        m = _COLLECTIVE.search(line)
        if not m or m.group(1) not in shapes or computation in fused:
            continue
        kind = m.group(2)
        if kind == "fusion":
            wrapped = re.search(
                r"calls=%?[\w.\-]*(reduce-scatter|all-reduce|all-gather)",
                line)
            if not wrapped:
                continue
            kind = wrapped.group(1)
        found.append((kind, computation in bodies))
    return found


def test_the_head_is_gathered_once_a_step_not_once_a_loss_chunk(
        v5e_host, as_tpu):
    """``train-nemo12b-4chip``'s loss at tiny widths (vocabulary 4096, so
    that no other operand has the head's shapes; two loss chunks): under
    ``fsdp=2 x tp=2`` ``lm_head`` ``[256, 4096]`` lies ``[128, 2048]`` a
    chip and is ``[256, 2048]`` once gathered over ``fsdp``. No collective
    with either shape stands inside a ``while`` body, and the whole step
    has at most two such gathers and exactly one such reduction, the
    gradient's over ``fsdp`` after the backward scan.

    Fails on the tree before PR 55 (an all-gather in each loss scan's
    body and an ``all-reduce-scatter`` fusion in the backward one's: the
    head gathered and its gradient reduced once a chunk) and passes
    since (models/llama.py:causal_lm_loss)."""
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh(devices=v5e_host, dp=1, fsdp=2, tp=2)
    cfg = _dense_cfg(vocab_size=4096)
    M, V = cfg.hidden_size, cfg.vocab_size
    text = _dense_train_step(cfg, mesh)
    assert len(re.findall(r"body=", text)) >= 3   # layers and both losses
    found = _head_collectives(text, {f"{M},{V // 2}", f"{M // 2},{V // 2}"})
    assert found
    assert not [kind for kind, in_body in found if in_body], found
    kinds = [kind for kind, _ in found]
    assert 1 <= kinds.count("all-gather") <= 2, found
    assert len(kinds) - kinds.count("all-gather") == 1, found


_MOVES_ROWS = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def _collectives_in_loops(text):
    """``(kind, result shapes, body)`` of every collective that stands in
    a ``while`` body of a compiled step's text (an asynchronous one at
    its ``-start``), a fusion that wraps one under the wrapped kind."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    found, computation = [], None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            computation = computation.lstrip("%")
            continue
        m = HLO_INSTRUCTION.match(line)
        if computation not in bodies or not m:
            continue
        result, opcode = m["result"], m["op"]
        kind = opcode.removesuffix("-start")
        if opcode == "fusion":
            wrapped = re.search(
                r"calls=%?[\w.\-]*(reduce-scatter|all-reduce|all-gather)",
                line)
            kind = wrapped.group(1) if wrapped else None
        if kind in _MOVES_ROWS:
            found.append((kind, set(re.findall(r"\w+\[([\d,]+)\]", result)),
                          computation))
    return found


def test_the_residual_lies_over_tp_between_the_matmul_pairs(either_way):
    """``train-nemo12b-4chip``'s layers at tiny widths under
    ``fsdp=2 x tp=2``: a chip's residual is ``[b, S, M]`` = [2, 512, 256].
    No all-reduce (nor a fusion that wraps one) with that result stands
    in a ``while`` body, forward or backward: between a row-parallel
    matmul and the next column-parallel one the rows lie split over
    ``tp``, and what moves them there has ``[b, S/2, M]``, a
    collective-permute beside the matmuls in both of the layer scans'
    bodies (parallel/collective_matmul.py).

    Fails on the tree before PR 61: there each of the two bodies holds
    four all-reduces of ``[2, 512, 256]`` (``wo``'s and ``w_down``'s
    outputs summed whole, nothing running beside them) and nothing of
    ``[2, 256, 256]``."""
    b, S, M = 4 // 2, 512, _dense_cfg().hidden_size
    train, _ = either_way[1][0]
    found = _collectives_in_loops(train)
    whole, half = f"{b},{S},{M}", f"{b},{S // 2},{M}"
    assert not [(kind, body) for kind, shapes, body in found
                if kind == "all-reduce" and whole in shapes], found
    halves = [(kind, body) for kind, shapes, body in found
              if half in shapes]
    assert {kind for kind, _ in halves} <= {
        "collective-permute", "reduce-scatter", "all-gather"}, halves
    # Forward and backward scan, a hop a matmul site a layer or more.
    per_body = {body: sum(1 for _, at in halves if at == body)
                for _, body in halves}
    assert len(per_body) == 2 and min(per_body.values()) >= 8, per_body


def test_one_device_step_holds_no_collective(v5e_host, as_tpu):
    """``train-mistral7b-1chip``'s side of the same rule: on a mesh of
    one described v5e device every axis is pruned, the ring's size is 1
    and the compiled step's text holds no collective of any kind."""
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.collective_matmul import ring_size

    mesh = make_mesh(devices=v5e_host[:1], dp=1, fsdp=1, tp=1)
    assert ring_size(mesh, 512) == 1
    text = _dense_train_step(_dense_cfg(), mesh)
    assert "tpu_custom_call" in text              # the flash kernels
    assert not re.search("|".join(_MOVES_ROWS), text)


def _flash_calls(text, rows):
    """How often each of the three flash kernels stands in a compiled
    step's text, ``(forward, dq, dkv)``, each known by the name the
    benchmark's trace reader gives its custom call: what it writes, for
    ``rows`` = batch x heads a device of 512 tokens by 128."""
    o = f"bf16_{rows}_512_128"
    names = [trace_reduce.stable_name(line.strip())
             for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    counts = tuple(names.count(name) for name in (
        f"pallas_{o}_f32_{rows}_1_512", f"pallas_{o}", f"pallas_{o}_{o}"))
    assert sum(counts) == len(names), names
    return counts


@pytest.mark.parametrize("policy,devices,forwards", [
    ("dots", 1, 2), ("dots", 4, 2), ("mlp", 1, 4), ("mlp", 4, 4),
    ("full", 1, 4)])
def test_the_flash_forward_runs_once_a_layer_under_dots(
        v5e_host, as_tpu, policy, devices, forwards):
    """Both train cells' step at tiny widths (``scan_chunk=2``: two
    layers unrolled in each scan's body; b4 x 512, two heads of 128):
    under ``"dots"`` the flash forward kernel's call stands once a layer
    of the chunk, in the forward scan's body, on one described v5e device
    as under ``fsdp=2 x tp=2`` (there inside the ``shard_map``, two rows a
    device). The policy keeps the ``o`` and ``lse`` that
    ``ops/flash_attention.py:_core_fwd`` names, so the backward scan's
    body holds the two backward kernels a layer and no forward. Under
    ``"mlp"`` and ``"full"``, which do not save the name, the backward
    body runs the forward again: twice a layer, four in the text.

    Fails on the tree before PR 74, where ``"dots"`` too reads
    ``(4, 2, 2)``: a Pallas call is no dot."""
    from ray_tpu.parallel import make_mesh

    split = 2 if devices == 4 else 1
    mesh = make_mesh(devices=v5e_host[:devices], dp=1, fsdp=split, tp=split)
    text = _dense_train_step(
        dataclasses.replace(_dense_cfg(), remat_policy=policy), mesh)
    assert _flash_calls(text, 4 * 2 // devices) == (forwards, 2, 2)


def test_the_kept_o_costs_no_pass_of_its_own_without_a_mesh(v5e, as_tpu):
    """``train-mistral7b-1chip`` builds its step with no mesh, so the
    kernels stand in the chunk itself and not in a ``shard_map``. There
    too the forward runs once a layer under ``"dots"``, and the kept
    ``o`` ``[8, 512, 128]`` comes out of ``_core_fwd``'s one jitted call
    with nothing of the forward reading it: ``jax.checkpoint`` puts no
    ``reduce_precision`` behind it, which behind a custom call is no
    fusion's epilogue but a pass over ``o`` (1.6 ms of the cell's step,
    PERF.md, PR 74; under a mesh the ``shard_map``'s own boundary did
    the same).

    Fails with ``out`` re-laid from the named ``o3`` outside that call:
    two such passes, one a layer of the chunk."""
    text = _dense_train_step(_dense_cfg(), None, v5e)
    assert _flash_calls(text, 4 * 2) == (2, 2, 2)
    assert not re.findall(r"bf16\[8,512,128\]\S* reduce-precision\(", text)
