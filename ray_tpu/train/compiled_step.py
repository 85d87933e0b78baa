"""One fused, compiled training step that survives real model depth.

The bench's previous hot path composed the step in the train loop (a
``jax.value_and_grad`` + optax update jitted ad hoc per caller); the
full-depth scan schedule OOM'd at 16.4 GB with 43-46% allocator
fragmentation (a v5e A/B before PR 1) because the stacked ``[L, ...]`` scan
residuals plus host-staged init buffers shattered the HBM arena. This
module is the single train-step authority (ROADMAP item 3):

- **One XLA program** per step: forward (chunked-scan schedule,
  models/llama.py), backward, optimizer update and — under a mesh — the
  GSPMD-inserted grad all-reduces, compiled together via pjit (jax.jit
  with shardings) so XLA schedules collectives against compute. Two
  things are placed by hand. The chunked loss's head: left to GSPMD it
  is gathered and its gradient reduced once a loss chunk, so
  models/llama.py:causal_lm_loss asks for it whole before the scans. And
  ``tp``'s collectives round the layers' matmul pairs: left to GSPMD
  each is one all-reduce with nothing beside it, so under a mesh whose
  ``tp`` axis is over 1 the residual lies split along the sequence over
  it and the reduce-scatter and all-gather go round a ring a chunk at a
  time beside the matmuls (parallel/collective_matmul.py, called from
  models/llama.py). ``fsdp``'s weight gathers and gradient reductions
  and every collective of the loss are GSPMD's.
- **In-place buffer donation**: params + optimizer state donate their
  buffers into the step (``donate_argnums=(0, 1)``) — the update aliases
  the old arena instead of doubling it.
- **Donation-friendly init**: :meth:`init` materializes params AND
  optimizer state in one compiled program, sharding-constrained in-graph
  (parallel/sharding.py), so every persistent buffer is allocated by the
  same allocator pass with its final layout — no host-staged arrays
  fragmenting the arena before training starts.
- **Compile + HBM telemetry**: jits through
  ``util/device_metrics.instrumented_jit(sample_memory=True)`` (the
  serve/llm.py wiring), so ``rtpu metrics`` shows train compile cache
  hits and the per-device peak/fragmentation gauges.

Ref analogue: the reference delegates all of this to the user's torch
loop; a TPU-native framework owns the compiled step the way it owns the
serving decode loop.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..models.llama import (
    LlamaConfig,
    causal_lm_loss,
    init_params,
    num_params,
    param_logical_axes,
    scan_chunks,
)
from ..parallel.sharding import (
    DEFAULT_RULES,
    constrain_pytree,
    named_sharding,
    tree_shardings,
)
from ..util import device_metrics


def _constrain_opt_state(tx, opt_state, mesh, axes_tree, rules):
    """Pin the optimizer state's param-shaped leaves (adam m/v) to the
    same shardings as their parameters; scalars (step count) pass
    through untouched."""
    shardings = tree_shardings(mesh, axes_tree, rules)
    return optax.tree_map_params(
        tx,
        lambda s, sh: jax.lax.with_sharding_constraint(s, sh),
        opt_state,
        shardings,
        transform_non_params=lambda s: s,
    )


class CompiledTrainStep:
    """Fused train step for the Llama family: loss + grad + optimizer +
    collectives in one donated XLA program.

    >>> step = CompiledTrainStep(cfg, mesh=mesh)
    >>> params, opt_state = step.init(jax.random.PRNGKey(0))
    >>> params, opt_state, loss = step(params, opt_state, tokens)

    ``mesh=None`` compiles for the local device set with no explicit
    shardings (single chip / CPU tests); a mesh routes params through
    the logical-axis rules (parallel/sharding.py) and batches over
    dp+fsdp.
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        *,
        mesh=None,
        optimizer: Optional[optax.GradientTransformation] = None,
        learning_rate: float = 1e-3,
        rules=DEFAULT_RULES,
        aux_weight: float = 0.01,
        donate: bool = True,
    ):
        scan_chunks(cfg)  # validate the chunk schedule up front
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules
        self.tx = optimizer or optax.adamw(learning_rate)
        self.donate = donate
        self._axes = param_logical_axes(cfg)

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: causal_lm_loss(
                    p, tokens, cfg, mesh, aux_weight=aux_weight
                )
            )(params)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        jit_kwargs: Dict[str, Any] = {}
        if donate:
            jit_kwargs["donate_argnums"] = (0, 1)
        self._step = device_metrics.instrumented_jit(
            train_step, sample_memory=True, **jit_kwargs
        )

        def _init(key):
            params = init_params(cfg, key)
            opt_state = self.tx.init(params)
            if mesh is not None:
                params = constrain_pytree(params, mesh, self._axes, rules)
                opt_state = _constrain_opt_state(
                    self.tx, opt_state, mesh, self._axes, rules
                )
            return params, opt_state

        self._init = jax.jit(_init)
        self._step_num = 0  # calls so far, for the profiler's step groups

    # ------------------------------------------------------------ state

    def init(self, key: jax.Array) -> Tuple[Any, Any]:
        """Materialize (params, opt_state) in ONE compiled program with
        their final shardings — the donation-friendly arena layout (every
        persistent buffer placed by one allocator pass, nothing staged
        through host arrays).

        Traced under ``jax.threefry_partitionable``: the legacy threefry
        lowering generates DIFFERENT values when XLA partitions the RNG
        op to satisfy a sharded output, so the same seed would produce
        different params on different meshes (and differ from the
        single-device init). The partitionable lowering is
        sharding-invariant by construction — one seed, one model,
        regardless of mesh shape."""
        with jax.threefry_partitionable(True):
            return self._init(key)

    def token_sharding(self):
        """Sharding for the [B, S] token batch under the mesh (batch
        over dp+fsdp), or None off-mesh — hand this to the input
        pipeline so device_put lands batches pre-sharded."""
        if self.mesh is None:
            return None
        return named_sharding(self.mesh, ("batch", "seq"), self.rules)

    # ------------------------------------------------------------- step

    def __call__(self, params, opt_state, tokens):
        """One fused step: returns (params, opt_state, loss). The input
        params/opt_state buffers are DONATED — dead after the call.

        The call returns when the step is dispatched, not when the device
        has run it. Each call records a ``train_step`` span of that
        DISPATCH under the rank's active trace (no-op outside one): a
        gang's waterfall shows step cadence beside checkpoint
        save/restore windows, and a span much longer than its
        neighbours is a host that waited for a free dispatch slot. The
        device's time for a step is in a profiled run, where the
        ``StepTraceAnnotation`` groups the device's work by step number."""
        import time as _time

        from ..core.timeline import record_span

        t0 = _time.time()
        try:
            with jax.profiler.StepTraceAnnotation(
                    "train_step", step_num=self._step_num):
                return self._step(params, opt_state, tokens)
        finally:
            self._step_num += 1
            try:
                record_span("train_step", t0, _time.time())
            # A lost span only blanks telemetry, never a step.
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    # ------------------------------------------------------ diagnostics

    def num_params(self, params) -> int:
        return num_params(params)

    def compile_stats(self) -> Dict[str, Any]:
        """Executable-cache telemetry for this step (also published as
        ray_tpu_device_jit_* series through the KV metrics pipeline)."""
        return {
            "fn": "train_step",
            "executables": int(self._step.__wrapped_jit__._cache_size()),
        }

    def memory_snapshot(self) -> Dict[str, Any]:
        """The HBM/allocator probe for the step's device: live + peak +
        reserved bytes and the fragmentation ratio (bench ab_matrix rows
        record exactly this dict)."""
        return device_metrics.hbm_snapshot()
