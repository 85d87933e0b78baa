"""Chip smoke: the train and serve paths on the TPU, through the entry
points a user calls, at the full width of the 8B-shaped Llama config.

    python chip_smoke.py            # one chip: train phase, serve phase
    python chip_smoke.py --chips 4  # sharded step vs one device, only

One chip (what the driver runs):

* train — ``ray_tpu.init`` -> ``JaxTrainer(ScalingConfig(num_workers=1,
  use_tpu=True))`` -> ``CompiledTrainStep`` fed by ``iter_jax_batches``,
  b8 x 2048: a warm-up step plus three, losses finite and decreasing,
  one executable, allocator stats present, the Pallas flash kernel in
  the compiled step. A second, fresh gang repeats the first step from
  the same seed: it must find the program in the persistent compile
  cache and reproduce the first gang's loss.
* serve — ``serve.run(LLMDeployment)`` on a replica that asks for the
  chip, per-node HTTP proxy, unary requests and one SSE stream with
  prompts of several hundred tokens (the flash prefill bucket) and 48
  new tokens each. Every generated token is checked against a plain
  full-sequence ``forward()`` on the same parameters: within a stated
  bf16 margin of its best logit, and for a float32 model the best one.

Four chips (run by the builder, never by the driver): one gang worker
holding ``TPU: 4`` takes the same train steps on one of its devices
and then on a ``fsdp=2, tp=2`` mesh over all four; losses must agree,
every matrix leaf must be split, the compiled step must hold the
collectives, and a device's peak memory must be a fraction of the
one-device run's.

Depth is cut to 4 layers (the published 32 need ~64 GB beside adamw
state; one v5e has 16) and the weights are random, made from ``SEED``.

The driver process never imports jax: each phase's worker owns the chip
and has exited before the next phase starts. Any failed check raises,
so the exit code is non-zero and no result line is printed. The last
line of stdout is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

SEED = 0

# Llama-3-8B layer geometry, vocabulary 32,768, depth cut to 4. A plain
# dict — a LlamaConfig holds a jax dtype, and this process must not
# import jax; the workers build the config from it.
MODEL_8B_SHAPED = {
    "vocab_size": 32_768,
    "hidden_size": 4096,
    "intermediate_size": 14_336,
    "num_layers": 4,
    "num_heads": 32,
    "num_kv_heads": 8,
    "dtype": "bfloat16",
    "remat_policy": "dots",
    "loss_chunk": 512,
    "scan_layers": True,
    "scan_chunk": 4,
}

# adamw, no warm-up. CompiledTrainStep's default 1e-3 suits the tiny test
# model; at hidden 4096 it took the loss from 10.9 to 15.1 in one step
# (v5e, PR 21). 1e-5 is that default scaled by 64/4096, the ratio of the
# two widths, and the largest of {1e-3, 3e-4, 1e-4, 3e-5, 1e-5} whose
# first four losses fell when rehearsed at full width on the CPU.
LEARNING_RATE_8B_SHAPED = 1e-5

# How far the logit of a token the engine chose may trail the best logit
# of the plain full-sequence forward on the same context. In float32 not
# at all: every token has to be the reference's argmax, which is greedy
# decoding token for token. In bfloat16 it cannot be: random weights put
# two of 32,768 logits within rounding of each other, and two paths
# that round differently then part for good (on the v5e 4 of 5 requests
# left a dense-cache XLA decode after 5 to 43 equal tokens; PR 21). The
# engine picks from a bf16 product, so logits closer than a bf16 ulp
# look the same to it: logits here reach ~4, where an ulp is 2**-6, and
# the bound is two of them. Measured: 238 of 240 tokens are the
# reference's argmax, the other two trail it by at most 0.0065 (v5e,
# PR 21).
LOGIT_MARGIN_TOL = {"float32": 0.0, "bfloat16": 2.0 ** -5}

# Sharded vs one-device loss, relative. Both runs share seed, data and
# program; they differ in where bf16 rounding falls (tp splits the wo
# and w_down contractions into per-device partial sums, each rounded to
# bf16 before the all-reduce), and adam's normalised update carries that
# from step to step. A quarter of a bf16 ulp (2**-8 = 3.9e-3) of the
# loss: the 2x2 v5e host measured 1.4e-5 over three steps (PR 21), and
# the CPU float32 test (tests/test_train_step.py) holds the same
# comparison to 1e-4.
SHARDED_LOSS_RTOL = 1e-3


def log(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


# ------------------------------------------------------ inside the workers


def _llama_config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    return LlamaConfig(**{**model, "dtype": jnp.dtype(model["dtype"])})


def _device_facts(platform: str) -> dict:
    """This process's device as jax reports it; raises unless it is the
    platform the phase was told to expect."""
    import jax

    device = jax.devices()[0]
    if device.platform != platform:
        raise RuntimeError(
            f"expected a {platform!r} device, jax found "
            f"{device.platform!r} ({device.device_kind})"
        )
    return {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}


class _CompileLog:
    """Counts what jax's own monitoring events say about compilation in
    this process: persistent-cache hits and misses (a miss is counted
    when an entry is written, i.e. for compiles over jax's 1 s floor)
    and seconds inside backend compile-or-load."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration
        )

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def facts(self) -> dict:
        import jax

        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "compile_s": round(self.seconds, 2),
                "cache_dir": jax.config.jax_compilation_cache_dir}


def _peak_hbm(hbm: dict):
    """Peak device bytes from an allocator snapshot: this libtpu counts
    live arrays under ``in_use`` and compiled programs' temporaries
    under ``reserved``. None where the backend keeps no stats (CPU)."""
    if not hbm:
        return None
    return hbm["peak_bytes_in_use"] + hbm["peak_bytes_reserved"]


def _zipf_tokens(vocab: int, shape, seed: int):
    """Token ids with Zipfian unigram statistics, so a few steps of
    training have something to learn (uniform noise has nothing)."""
    import numpy as np

    p = 1.0 / np.arange(1, vocab + 1)
    return np.random.RandomState(seed).choice(
        vocab, size=shape, p=p / p.sum()
    ).astype(np.int32)


def _take_steps(step, params, opt_state, tokens, batch: int, sharding=None):
    """Feed ``tokens`` through the step in ``batch``-row batches from
    ``iter_jax_batches``; returns the state and the loss of each step."""
    from ray_tpu import data as rd
    from ray_tpu import train as rt_train
    from ray_tpu.data.context import DataContext

    # The gang worker is the compute process; block tasks run inline.
    DataContext.get_current().use_remote_tasks = False
    ds = rd.from_numpy(tokens, column="tokens")
    losses, seconds = [], []
    # zero_copy=False is what a TPU gets anyway. The CPU default, a
    # dlpack alias, hands over committed arrays, and the step's second
    # call (now with committed state too) is then a second executable.
    for item in ds.iter_jax_batches(batch_size=batch, device=sharding,
                                    drop_last=True, zero_copy=False):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, item["tokens"])
        losses.append(float(loss))  # host read: the step has finished
        seconds.append(round(time.perf_counter() - t0, 3))
        rt_train.report({"step": len(losses), "loss": losses[-1]})
    # The first step's seconds hold its compile (or cache load).
    return params, opt_state, item["tokens"], losses, seconds


def _compiled_step_text(step, params, opt_state, tokens) -> str:
    """Optimised HLO of the step program, through the AOT passthrough.
    The persistent cache holds it by now, so this loads, not compiles."""
    return step._step.__wrapped_jit__.lower(
        params, opt_state, tokens
    ).compile().as_text()


def _train_loop(config):
    import jax

    from ray_tpu import train as rt_train
    from ray_tpu.train.compiled_step import CompiledTrainStep

    compiles = _CompileLog()  # before the first compile
    device = _device_facts(config["platform"])
    cfg = _llama_config(config["model"])
    batch, seqlen = config["batch"], config["seqlen"]
    step = CompiledTrainStep(cfg, learning_rate=config["learning_rate"])
    params, opt_state = step.init(jax.random.PRNGKey(config["seed"]))
    tokens = _zipf_tokens(
        cfg.vocab_size, (config["steps"] * batch, seqlen + 1),
        config["seed"],
    )
    params, opt_state, last, losses, seconds = _take_steps(
        step, params, opt_state, tokens, batch
    )
    facts = {
        "pid": os.getpid(),
        "device": device,
        "losses": losses,
        "step_s": seconds,
        "num_params": step.num_params(params),
        "executables": step.compile_stats()["executables"],
        "hbm": step.memory_snapshot(),
        **compiles.facts(),
    }
    facts["peak_hbm_bytes"] = _peak_hbm(facts["hbm"])
    if config["inspect"]:
        facts["tpu_custom_calls"] = _compiled_step_text(
            step, params, opt_state, last
        ).count("tpu_custom_call")
    rt_train.report(facts)


def _leaf_placement(tree, min_split_bytes: int = 1 << 20) -> dict:
    """How a pytree of arrays lies on the devices: every leaf must be on
    every device of its sharding's mesh, and every leaf of a MiB or more
    must be split (a shard smaller than the leaf). Small leaves the
    rules replicate (norm scales, adam's count) are listed, not hidden."""
    import jax

    whole, replicated = [], []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = jax.tree_util.keystr(path)
        shard = leaf.addressable_shards[0].data
        if len(leaf.sharding.device_set) < 2:
            whole.append(name)
        elif shard.size == leaf.size:
            if leaf.nbytes >= min_split_bytes:
                whole.append(name)
            else:
                replicated.append(name)
    return {"whole_on_one_device": whole, "replicated_small": replicated}


def _sharded_loop(config):
    """Four chips, one process: the seed and batches first on one
    device, then on a fsdp=2 x tp=2 mesh over all four.

    One device goes first because a loaded program keeps its temporaries
    reserved: after it, device 0 still fits its quarter of the sharded
    run, the other way round it might not fit the whole model. Device
    0's peak then belongs to the one-device run, and the sharded run's
    per-device peak is read from the three devices it alone touched."""
    import gc

    import jax
    import numpy as np

    from ray_tpu import train as rt_train
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train.compiled_step import CompiledTrainStep
    from ray_tpu.util.device_metrics import hbm_snapshot

    device = _device_facts(config["platform"])
    cfg = _llama_config(config["model"])
    batch, seqlen = config["batch"], config["seqlen"]
    lr = config["learning_rate"]
    key = jax.random.PRNGKey(config["seed"])
    tokens = _zipf_tokens(
        cfg.vocab_size, (config["steps"] * batch, seqlen + 1),
        config["seed"],
    )
    mesh = make_mesh(dp=1, fsdp=2, tp=2)
    devices = list(mesh.devices.flat)

    single = CompiledTrainStep(cfg, learning_rate=lr)
    params, opt_state = single.init(key)
    params, opt_state, last, single_losses, single_s = _take_steps(
        single, params, opt_state, tokens, batch
    )
    single_peak = _peak_hbm(hbm_snapshot(devices[0]))
    del params, opt_state, last, single
    jax.clear_caches()  # unload the one-device programs
    gc.collect()

    step = CompiledTrainStep(cfg, mesh=mesh, learning_rate=lr)
    params, opt_state = step.init(key)
    placement = {
        "params": _leaf_placement(params),
        "opt_state": _leaf_placement(opt_state),
    }
    params, opt_state, last, sharded_losses, sharded_s = _take_steps(
        step, params, opt_state, tokens, batch, step.token_sharding()
    )
    text = _compiled_step_text(step, params, opt_state, last)
    rt_train.report({
        "pid": os.getpid(),
        "device": device,
        # make_mesh reshapes jax.devices() in list order: which chip
        # (by id and physical coords) sits at each (fsdp, tp) position.
        "mesh": [
            {"fsdp": i, "tp": j, "id": d.id,
             "coords": list(getattr(d, "coords", ()))}
            for (i, j), d in np.ndenumerate(
                mesh.devices.reshape(mesh.shape["fsdp"], mesh.shape["tp"])
            )
        ],
        "placement": placement,
        "sharded_losses": sharded_losses,
        "single_losses": single_losses,
        "sharded_step_s": sharded_s,
        "single_step_s": single_s,
        "single_peak_bytes": single_peak,
        "sharded_peak_bytes": [
            _peak_hbm(hbm_snapshot(d)) for d in devices[1:]
        ],
        "collectives": {
            op: text.count(op + "(")
            for op in ("all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute")
        },
        "tpu_custom_calls": text.count("tpu_custom_call"),
    })


def _llm_deployment():
    """The deployment class, built on first use: importing this module
    must not import the serving stack."""
    from ray_tpu.serve.llm import LLMDeployment

    class ProbedLLM(LLMDeployment):
        """``LLMDeployment`` built from the plain-dict model, plus what
        the smoke has to ask from inside the replica: requests go
        through the inherited ``__call__`` and ``stream``."""

        def __init__(self, model: dict, platform: str, **engine_kwargs):
            self._compiles = _CompileLog()  # before the first compile
            self._device = _device_facts(platform)
            super().__init__(_llama_config(model), **engine_kwargs)

        def margins(self, prompts, outputs):
            """Teacher-forced check of every generated token: one plain
            full-sequence forward (XLA attention, no cache) over prompt
            + output with the vocabulary projection in float32, and for
            each output position the gap between the reference's best
            logit and the logit of the token the engine chose there —
            0 where the engine's token is the reference's argmax."""
            import dataclasses

            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models.llama import hidden_forward

            cfg = dataclasses.replace(self.engine.cfg, use_flash=False)
            n_prompt = len(prompts[0])
            seqs = jnp.asarray(
                [p + o for p, o in zip(prompts, outputs)], jnp.int32
            )

            @jax.jit
            def gaps(params, seqs):
                x, _ = hidden_forward(params, seqs[:, :-1], cfg)
                # One float32 array that both the max and the gather
                # read: a bf16 product lets XLA round one and not the
                # other, which shows as a one-ulp "margin" everywhere.
                logits = jnp.einsum(
                    "bsm,mv->bsv",
                    x[:, n_prompt - 1:].astype(jnp.float32),
                    params["lm_head"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )
                chosen = jnp.take_along_axis(
                    logits, seqs[:, n_prompt:, None], axis=-1
                )[..., 0]
                return logits.max(axis=-1) - chosen

            return np.asarray(gaps(self.engine.params, seqs)).tolist()

        def probe(self, prompt_len: int) -> dict:
            import jax

            from ray_tpu.util.device_metrics import hbm_snapshot

            eng = self.engine
            bucket = eng.scheduler.bucket(prompt_len)
            i32 = jax.numpy.int32
            shape = jax.ShapeDtypeStruct
            prefill = eng.runner.prefill.__wrapped_jit__.lower(
                eng.runner.params, eng.runner.cache,
                shape((eng.max_batch,), i32),
                shape((1, bucket), i32), shape((), i32), shape((), i32),
                {kind: shape((min(bucket // eng.page_size, columns),), i32)
                 for kind, (_, _, columns) in eng.books.pools.items()},
            ).compile().as_text()
            return {
                "pid": os.getpid(),
                "device": self._device,
                "hbm": hbm_snapshot(),
                "prefill_bucket": bucket,
                "prefill_tpu_custom_calls":
                    prefill.count("tpu_custom_call"),
                "engine": eng.stats(),
                **self._compiles.facts(),
            }

    return ProbedLLM


# --------------------------------------------------------- in the driver


def _exited(pid: int) -> bool:
    """Gone, or a zombie with no thread left (``benchmark/driver.py``'s
    rule, PR 51): a zombie leader whose other threads live has not
    exited, they still hold its chips."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            # "pid (comm) state ...": the state is field 3 and
            # num_threads field 20.
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return True
    return fields[0] == "Z" and int(fields[17]) <= 1


def _wait_chip_released(pid: int, timeout: float = 60.0) -> float:
    """Block until the phase's worker process has exited and no ``tpu``
    worker is left in the pool; returns the seconds it took."""
    from ray_tpu.util import state

    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        live = [w for w in state.list_workers(
                    filters=[("worker_type", "=", "tpu")])
                if w["state"] != "dead"]
        if _exited(pid) and not live:
            return round(time.monotonic() - t0, 2)
        time.sleep(0.1)
    raise RuntimeError(
        f"tpu worker pid {pid} still holds the chip {timeout}s after its "
        f"phase ended (live tpu workers: {live})"
    )


def build_native() -> dict:
    """``make native`` from the tracked sources, then load both
    extensions. With a toolchain, a build or load failure is fatal;
    without one the pure-Python store and pump run, and the line says
    so."""
    from ray_tpu import _native

    toolchain = bool(shutil.which("make") and shutil.which("g++"))
    if toolchain:
        proc = _native.build()
        if proc.returncode != 0:
            raise RuntimeError(
                "make native failed:\n" + (proc.stderr or proc.stdout)[-2000:]
            )
    else:
        os.environ["RAY_TPU_NO_NATIVE_BUILD"] = "1"
    store = _native.load_rtstore() is not None
    pump = _native.load_rtpump() is not None
    if toolchain and not (store and pump):
        raise RuntimeError(
            f"built but could not load: _rtstore={store} _rtpump={pump}"
        )
    return {"toolchain": toolchain,
            "store": "native" if store else "python",
            "pump": "native" if pump else "python"}


def _fit(loop, config: dict, tpus: int) -> dict:
    """One fresh gang worker holding ``tpus`` chips; returns its last
    report once that worker has exited."""
    from ray_tpu.train import (
        FailureConfig, JaxTrainer, RunConfig, ScalingConfig,
    )

    result = JaxTrainer(
        loop,
        train_loop_config=config,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"TPU": tpus},
        ),
        run_config=RunConfig(
            name="chip-smoke",
            failure_config=FailureConfig(max_failures=0),
        ),
    ).fit()
    if result.error is not None:
        raise result.error
    facts = result.metrics
    facts["released_s"] = _wait_chip_released(facts["pid"])
    return facts


def _check_losses(losses, steps: int) -> None:
    if len(losses) != steps:
        raise RuntimeError(f"took {len(losses)} steps, wanted {steps}")
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise RuntimeError(f"loss not finite: {losses}")
    if not all(b < a for a, b in zip(losses, losses[1:])):
        raise RuntimeError(f"loss not decreasing: {losses}")


def train_phase(model: dict, *, platform: str, batch: int, seqlen: int,
                steps: int, learning_rate: float) -> dict:
    """Gang 1 takes ``steps`` steps (the first is the warm-up that
    compiles); gang 2, a fresh process, repeats step one."""
    config = {"model": model, "platform": platform, "batch": batch,
              "seqlen": seqlen, "learning_rate": learning_rate,
              "seed": SEED}
    first = _fit(_train_loop, {**config, "steps": steps, "inspect": True},
                 tpus=1)
    log("train", gang=1, **first)
    _check_losses(first["losses"], steps)
    if first["executables"] != 1:
        raise RuntimeError(f"step recompiled: {first['executables']}")
    second = _fit(_train_loop, {**config, "steps": 1, "inspect": False},
                  tpus=1)
    log("train", gang=2, **second)
    if second["losses"][0] != first["losses"][0]:
        raise RuntimeError(
            f"same seed, another first loss: {second['losses'][0]} vs "
            f"{first['losses'][0]}"
        )
    if platform == "tpu":
        if not first["hbm"]:
            raise RuntimeError("the TPU reported no memory_stats()")
        if not first["tpu_custom_calls"]:
            raise RuntimeError("no Pallas kernel in the compiled step")
        if not second["cache_hits"] or second["cache_misses"]:
            raise RuntimeError(
                f"second gang recompiled: {second['cache_hits']} hits, "
                f"{second['cache_misses']} misses in {second['cache_dir']}"
            )
    return first["device"]


def _post(url: str, body: dict, timeout: float = 300.0):
    return urllib.request.urlopen(urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    ), timeout=timeout)


def _unary(url: str, body: dict):
    with _post(url, body) as r:
        return json.loads(r.read())["result"]["tokens"]


def _sse(url: str, body: dict):
    tokens = []
    with _post(url + "/stream", body) as r:
        if r.headers.get("Content-Type") != "text/event-stream":
            raise RuntimeError(f"not an SSE reply: {dict(r.headers)}")
        event = None
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("event:"):
                event = line[6:].strip()
            elif line.startswith("data:") and event == "error":
                raise RuntimeError(f"stream failed: {line[5:].strip()}")
            elif line.startswith("data:") and event is None:
                tokens.append(json.loads(line[5:])["token"])
    return tokens


def serve_phase(model: dict, *, platform: str, prompt_len: int,
                max_new_tokens: int, max_len: int, total_pages: int
                ) -> dict:
    """An ``LLMDeployment`` replica on a ``tpu`` worker behind the
    per-node HTTP proxy: a warm-up through the handle, then three unary
    requests and one SSE stream, all in flight together."""
    import random

    n_unary = 3

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import http_proxy

    rng = random.Random(SEED)
    prompts = [[rng.randrange(model["vocab_size"]) for _ in range(prompt_len)]
               for _ in range(n_unary + 2)]
    dep = serve.deployment(_llm_deployment()).options(
        name="llm",
        ray_actor_options={"max_concurrency": 8, "num_tpus": 1},
    )
    proxies = {}
    try:
        handle = serve.run(dep.bind(
            model, platform, max_batch=8, max_len=max_len, seed=SEED,
            total_pages=total_pages,
        ), name="llm")
        proxies = http_proxy.start_per_node_proxies(port=0)
        (_, port), = proxies.values()
        url = f"http://127.0.0.1:{port}/llm"

        t0 = time.monotonic()
        outputs = [handle.remote({
            "prompt": prompts[0], "max_new_tokens": max_new_tokens,
        }).result(timeout=900)["tokens"]]
        warmup_s = round(time.monotonic() - t0, 2)

        body = [{"prompt": p, "max_new_tokens": max_new_tokens}
                for p in prompts]
        with ThreadPoolExecutor(max_workers=n_unary + 1) as pool:
            calls = [pool.submit(_unary, url, b)
                     for b in body[1:n_unary + 1]]
            calls.append(pool.submit(_sse, url, body[-1]))
            outputs += [c.result() for c in calls]

        margins = handle.options(method="margins").remote(
            prompts, outputs
        ).result(timeout=900)
        facts = handle.options(method="probe").remote(
            prompt_len
        ).result(timeout=900)
    finally:
        for actor, _ in proxies.values():
            ray_tpu.get(actor.shutdown.remote(), timeout=30)
            ray_tpu.kill(actor)
        serve.shutdown()
    facts["released_s"] = _wait_chip_released(facts["pid"])
    tol = LOGIT_MARGIN_TOL[model["dtype"]]
    log("serve", requests={"handle": 1, "http_unary": n_unary, "sse": 1},
        prompt_len=prompt_len,
        new_tokens=[len(o) for o in outputs], warmup_s=warmup_s,
        argmax_agreement=[sum(m == 0 for m in row) for row in margins],
        max_logit_margin=max(max(row) for row in margins),
        logit_margin_tol=tol, **facts)

    if [len(o) for o in outputs] != [max_new_tokens] * len(prompts):
        raise RuntimeError(f"short output: {[len(o) for o in outputs]}")
    worst = max(max(row) for row in margins)
    if worst > tol:
        raise RuntimeError(
            f"an engine token trails the reference's best logit by "
            f"{worst:.4f} (> {tol}): margins {margins}"
        )
    if platform == "tpu":
        if not facts["hbm"]:
            raise RuntimeError("the TPU reported no memory_stats()")
        if not facts["prefill_tpu_custom_calls"]:
            raise RuntimeError("no Pallas kernel in the prefill program")
    return facts["device"]


def sharded_phase(model: dict, *, platform: str, batch: int, seqlen: int,
                  steps: int, learning_rate: float) -> dict:
    facts = _fit(_sharded_loop, {
        "model": model, "platform": platform, "batch": batch,
        "seqlen": seqlen, "steps": steps, "learning_rate": learning_rate,
        "seed": SEED,
    }, tpus=4)
    sharded, single = facts["sharded_losses"], facts["single_losses"]
    rel = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    log("sharded", loss_max_rel_diff=rel, loss_rtol=SHARDED_LOSS_RTOL,
        **facts)
    _check_losses(sharded, steps)
    _check_losses(single, steps)
    if rel > SHARDED_LOSS_RTOL:
        raise RuntimeError(
            f"sharded and one-device losses differ by {rel:.2e} "
            f"(> {SHARDED_LOSS_RTOL}): {sharded} vs {single}"
        )
    for tree, where in facts["placement"].items():
        if where["whole_on_one_device"]:
            raise RuntimeError(
                f"{tree} leaves not split over the mesh: "
                f"{where['whole_on_one_device']}"
            )
    if not (facts["collectives"]["all-gather"]
            and facts["collectives"]["all-reduce"]):
        raise RuntimeError(
            f"no collectives in the sharded step: {facts['collectives']}"
        )
    if platform == "tpu":
        if not facts["tpu_custom_calls"]:
            raise RuntimeError("no Pallas kernel in the sharded step")
        share = (max(facts["sharded_peak_bytes"])
                 / facts["single_peak_bytes"])
        log("sharded", peak_share_of_single=round(share, 3))
        # A quarter of the state plus activations that tp does not
        # split; whole copies on every device would be ~1.
        if share > 0.5:
            raise RuntimeError(
                f"per-device peak is {share:.0%} of the one-device run's"
            )
    return facts["device"]


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = parser.parse_args().chips

    log("native", **build_native())

    import ray_tpu
    from ray_tpu.core.tpu import local_chip_count, require_driver_off_jax

    log("host", chips_wanted=chips, chips_detected=local_chip_count(),
        jax_platforms=os.environ.get("JAX_PLATFORMS"),
        cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    ray_tpu.init(num_cpus=8, num_tpus=chips,
                 system_config={"log_to_driver": False})
    if chips == 4:
        phases = [lambda: sharded_phase(
            MODEL_8B_SHAPED, platform="tpu", batch=8, seqlen=2048, steps=3,
            learning_rate=LEARNING_RATE_8B_SHAPED)]
    else:
        phases = [
            lambda: train_phase(
                MODEL_8B_SHAPED, platform="tpu", batch=8, seqlen=2048,
                steps=4, learning_rate=LEARNING_RATE_8B_SHAPED),
            lambda: serve_phase(
                MODEL_8B_SHAPED, platform="tpu", prompt_len=320,
                max_new_tokens=48, max_len=1024, total_pages=4096),
        ]
    devices = []
    try:
        for phase in phases:
            # Workers spawn from a parent that holds no device, and no
            # phase's driver side may pull jax in either.
            require_driver_off_jax()
            devices.append(phase())
            require_driver_off_jax()
    finally:
        ray_tpu.shutdown()
    device = devices[0]
    if any(d != device for d in devices) or device["count"] != chips:
        raise RuntimeError(f"phases disagree on the device: {devices}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
