"""Job kind ``train``: a ``JaxTrainer`` gang worker running
``CompiledTrainStep`` fed by ``iter_jax_batches``, read every few steps.

From chip_smoke.py's ``_train_loop``/``_sharded_loop``/``_fit`` (PR 21).
The window is cut into readings of ``steps_per_reading`` steps, each
dispatched without a host sync and closed by one host read of the last
loss, as a job that logs every few steps does.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Mapping


def _loop(c: Mapping) -> None:
    """Inside the gang worker, which owns the chip(s)."""
    import gc
    import math

    import jax
    import numpy as np

    from ray_tpu import data as rd
    from ray_tpu import train as rt_train
    from ray_tpu.data.context import DataContext
    from ray_tpu.train.compiled_step import CompiledTrainStep

    from .. import arch, worker

    phases = {"worker_started": time.time()}
    compiles = worker.CompileLog()  # before the first compile
    device = worker.device_facts(c["chips"])
    config, traffic = c["config"], c["traffic"]
    cfg = arch.program_config(config)
    reference = arch.reference(config)
    mesh = None
    if config.get("mesh"):
        from ray_tpu.parallel import make_mesh

        mesh = make_mesh(**config["mesh"])
    step = CompiledTrainStep(
        cfg, mesh=mesh, learning_rate=config["trainer"]["learning_rate"])
    params, opt_state = jax.block_until_ready(
        step.init(worker.prng_key(c["seed"])))
    sharding = step.token_sharding()
    phases["weights_made"] = time.time()

    batch, seqlen = traffic["batch"], traffic["seqlen"]
    per_reading = traffic["steps_per_reading"]
    warmup = traffic["warmup_steps"]
    tokens = worker.zipf_tokens(
        cfg.vocab_size,
        ((warmup + traffic["max_steps"]) * batch, seqlen + 1), c["seed"])

    # correct, outside the window: the plain reference's loss on a
    # sample of the first measured batch's sequences, on the weights as
    # initialised, before the step's program takes its memory ...
    first = tokens[warmup * batch:(warmup + 1) * batch]
    k = traffic["check_sequences"]
    rows = np.random.default_rng(c["seed"]).choice(batch, k, replace=False)
    ref_loss = float(jax.jit(lambda p, t: reference.loss(p, t, config))(
        params, first[rows]))
    jax.clear_caches()  # unload the reference; it keeps scratch reserved
    gc.collect()
    phases["reference_done"] = time.time()
    # ... against the step's own loss on a batch made of those same
    # sequences. This is also the step that compiles, or loads.
    check = np.tile(first[rows], (batch // k, 1))
    params, opt_state, loss = step(
        params, opt_state,
        jax.device_put(check, sharding) if sharding else jax.numpy.asarray(check))
    sys_loss = float(loss)
    phases["step_loaded"] = time.time()

    DataContext.get_current().use_remote_tasks = False  # blocks inline
    batches = rd.from_numpy(tokens, column="tokens").iter_jax_batches(
        batch_size=batch, device=sharding, drop_last=True, zero_copy=False)
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state,
                                       next(batches)["tokens"])
    float(loss)

    tracer = worker.Tracer() if c["trace"] else None
    trace_at = 1  # the second reading on: the first absorbs the start-up
    trace = None
    programs_before = compiles.programs
    annotate = jax.profiler.TraceAnnotation
    readings, losses, input_wait_s = [], [], 0.0
    window_start = time.time()
    t_start = t = time.perf_counter()
    while True:
        if tracer and len(readings) == trace_at:
            tracer.start()
        pending = []
        for _ in range(per_reading):
            t_in = time.perf_counter()
            with annotate("bench.input_wait"):
                item = next(batches)
            input_wait_s += time.perf_counter() - t_in
            with annotate("bench.train_step"):
                params, opt_state, loss = step(params, opt_state,
                                               item["tokens"])
            pending.append(loss)
        with annotate("bench.read_loss"):
            float(pending[-1])  # host read: the reading's steps are done
        now = time.perf_counter()
        readings.append(now - t)
        losses += [float(x) for x in pending]
        if tracer and len(readings) == trace_at + traffic["trace_readings"]:
            trace = tracer.stop()
            tracer = None
            now = time.perf_counter()
        t = now
        steps = len(readings) * per_reading
        if (now - t_start >= c["seconds"]
                or steps + per_reading > traffic["max_steps"]):
            break
    if tracer and tracer.dir:
        trace = tracer.stop()

    rt_train.report({
        "pid": os.getpid(),
        "device": device,
        "window_start": window_start,
        "reading_s": readings,
        "tokens_per_reading": per_reading * batch * seqlen,
        "input_wait_s": input_wait_s,
        "losses_finite": all(math.isfinite(x) for x in losses),
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "check": {"system_loss": sys_loss, "reference_loss": ref_loss,
                  "atol": reference.LOSS_ATOL[config["dtype"]]},
        "programs_in_window": compiles.programs - programs_before,
        "phases": phases,
        "executables": step.compile_stats()["executables"],
        "num_params": step.num_params(params),
        "memory_peak_bytes": worker.memory_peak_bytes(),
        "trace": trace,
        **compiles.facts(),
    })


def run(cell: Mapping, config: Mapping, traffic: Mapping, seed: int,
        seconds: float, trace: bool) -> Dict:
    """In the driver: one fresh gang worker holding the cell's chips;
    returns its report once that worker has exited."""
    from ray_tpu.train import (
        FailureConfig, JaxTrainer, RunConfig, ScalingConfig,
    )

    from .. import driver

    with driver.system(cell["chips"]):
        result = JaxTrainer(
            _loop,
            train_loop_config={"config": config, "traffic": traffic,
                               "chips": cell["chips"], "seed": seed,
                               "seconds": seconds, "trace": trace},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": cell["chips"]}),
            run_config=RunConfig(
                name="benchmark",
                failure_config=FailureConfig(max_failures=0)),
        ).fit()
        if result.error is not None:
            raise result.error
        facts = result.metrics
        driver.wait_chip_released(facts["pid"])
    check = facts["check"]
    correct = (
        facts["losses_finite"]
        and facts["programs_in_window"] == 0
        and facts["executables"] == 1
        and abs(check["system_loss"] - check["reference_loss"])
        <= check["atol"]
    )
    steps = len(facts["reading_s"]) * traffic["steps_per_reading"]
    return {"correct": correct, "attempted": steps, "failed": 0,
            "worker": facts, "client": None, "trace": facts.pop("trace")}
