"""A configuration names its own architecture: the one place that reads
a configuration file's ``arch`` key.

``"arch": {"program_config": "benchmark.worker.llama_config",
"reference": "benchmark.reference", "counts": "benchmark.flops"}``

Each value is a dotted name, found as ``run.find_reader`` finds a
metric's reader: ``program_config`` names a function, the other two a
module. A configuration of another architecture is a ``configs`` entry,
its file, and new modules beside the old ones; no file that is there is
edited. A missing key, or a name outside ``BENCHMARK.json``'s ``paths``,
is an error: a default would be a second way in.

How a name is imported. With ``importlib.import_module``, the checkout
being on ``sys.path`` as ``python -m benchmark.run`` from its root puts
it. ``tests/bench_harness`` is one of the ``paths`` and no package:
neither it nor ``tests`` has an ``__init__.py``, so
``tests.bench_harness.moe_tiny.counts`` imports as a namespace package
from the same root. The name must spell a file under one of the
``paths``, and the module that came back must be that file, so a
``tests`` or ``benchmark`` elsewhere on the path cannot stand in.

What the callers use, and nothing more:

``program_config`` function: ``f(config) -> cfg``, the program's own
model config (jobs/train.py hands it to ``CompiledTrainStep``,
jobs/serve.py to ``init_params`` and ``LLMDeployment``). May import jax.

``reference`` module, the plain reference; imports jax, so only the
process that owns the chip resolves it. ``params`` is the tree the
program made, ``tokens`` an int array ``[B, S + 1]``, ``config`` the
configuration file's dict (closed over before ``jax.jit``: a dict is no
static argument):
    ``loss(params, tokens, config) -> scalar``, what the train step's
    loss on the same weights and tokens is held to;
    ``logit_margins(params, tokens, config) -> [B, S]``, how far the
    logit of each token that follows trails the best at its position;
    ``LOSS_ATOL``, ``LOGIT_MARGIN_TOL``: ``{dtype name: tolerance}``.

``counts`` module, operations and bytes from shapes; never imports jax
(the driver's readers call it):
    ``head_dim(config) -> int``
    ``param_counts(config) -> {"layer", "embed", "lm_head", "norms",
    "matmul", "total"}``
    ``train_flops_per_token(config, seqlen)``
    ``flash_train_flops(config, batch, seqlen)``
    ``flash_train_bytes(config, batch, seqlen)``
    ``kv_bytes_per_token(config)``
    ``decode_step_flops(config, sequences, context_tokens)``
    ``decode_step_bytes(config, sequences, context_tokens)``
The chip's own ``peaks`` and ``roofline_s`` stay in ``benchmark/flops.py``.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Mapping

from .driver import CHECKOUT

ROLES = ("program_config", "reference", "counts")


def _paths():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)["paths"]


def _module(dotted: str):
    """The module ``dotted`` names, if its file lies under one of the
    benchmark's ``paths`` in this checkout."""
    relative = dotted.replace(".", "/")
    roots = [os.path.join(CHECKOUT, p) + os.sep for p in _paths()]
    wanted = {os.path.join(CHECKOUT, relative + ".py"),
              os.path.join(CHECKOUT, relative, "__init__.py")}
    if not (all(part.isidentifier() for part in dotted.split("."))
            and any(w.startswith(r) for w in wanted for r in roots)):
        raise ValueError(
            f"arch names {dotted!r}, which is no dotted name of a module "
            f"or is outside BENCHMARK.json's paths {_paths()}: an "
            f"architecture's modules live with the benchmark")
    module = importlib.import_module(dotted)
    found = os.path.abspath(getattr(module, "__file__", None) or "")
    if found not in wanted:
        raise ValueError(
            f"arch names {dotted!r}, which imported from {found!r} and "
            f"not from this checkout's {sorted(wanted)}")
    return module


def _name(config: Mapping, role: str) -> str:
    names = config.get("arch")
    if not isinstance(names, Mapping) or not isinstance(names.get(role), str):
        raise ValueError(
            f"the configuration file has no arch.{role}: it must name its "
            f"own {', '.join(ROLES)} (there is no default architecture)")
    return names[role]


def program_config(config: Mapping):
    """The program's model config, built by the function the file names."""
    module, _, function = _name(config, "program_config").rpartition(".")
    return getattr(_module(module), function)(config)


def reference(config: Mapping):
    """The configuration's plain reference module. Imports jax."""
    return _module(_name(config, "reference"))


def counts(config: Mapping):
    """The configuration's module of operation and byte counts."""
    return _module(_name(config, "counts"))
