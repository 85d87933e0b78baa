"""Elastic-gang chaos acceptance → MULTICHIP_r06.json (`make train-chaos`).

The framework-level half of the multichip story (VERDICT "next #7"): the
gang is real worker PROCESSES under the full control plane, not threads.

Phases (CPU backend, 2 worker processes × 4 virtual devices each):

1. **rendezvous** — gang=2 ``JaxTrainer`` on the use_tpu path: rank 0
   reserves the coordinator port on its own host, the address is
   brokered through GCS KV, both ranks run ``jax.distributed.initialize``
   and assert ``process_count == 2`` with 8 global devices. (This box's
   CPU backend refuses cross-process collectives — the record notes it —
   so the phase proves the rendezvous + device plane, and per-process
   sharded math runs on each rank's 4-device mesh.)
2. **baseline** — deterministic elastic loop, uninterrupted.
3. **gang restart** — the ``train_worker`` fault point kills a rank
   mid-step (scoped to the live run id via the chaos plane); the
   supervisor aborts the gang and restarts from the last COMMITTED
   checkpoint; the final state must equal the baseline's. Gang-restart
   count and recovery seconds are recorded.
4. **checkpoint chaos** — a ``checkpoint_io`` fault during save crashes
   the attempt; restart falls back to the previous committed checkpoint
   (the torn save never became "latest").
5. **rolling restart** — ``Cluster.rolling_restart()`` under an active
   ``fit()``: the gang sees ``node_draining``, checkpoints, surrenders
   the node, restarts on the replacement; ≤ 1 step of work lost.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=4")
os.environ["XLA_FLAGS"] = " ".join(_flags)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

DEVICES_PER_PROC = 4
GANG = 2


# ------------------------------------------------------------- train loops


def make_rendezvous_loop():
    def loop(config):
        import jax

        from ray_tpu.train.session import get_session

        # jax.distributed.initialize already ran in the worker entry
        # (coordinator address brokered through GCS KV by the trainer).
        assert jax.process_count() == GANG, jax.process_count()
        n_local = len(jax.local_devices())
        n_global = len(jax.devices())
        assert n_global == GANG * n_local, (n_global, n_local)
        # Sharded math over THIS rank's 4-device mesh (cross-process
        # collectives are not implemented on the CPU backend; on TPU the
        # same program spans the slice).
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        local = jax.local_devices()
        mesh = Mesh(local, ("dp",))
        x = jax.device_put(
            jnp.arange(4 * len(local), dtype=jnp.float32),
            NamedSharding(mesh, P("dp")),
        )
        total = float(jax.jit(lambda v: (v * v).sum())(x))
        sess = get_session()
        sess.report({
            "total": total,
            "processes": jax.process_count(),
            "local_devices": n_local,
            "global_devices": n_global,
            "rank": sess.world_rank,
        })

    return loop


def make_elastic_loop():
    def loop(config):
        import os as _os
        import time as _time

        import jax.numpy as jnp

        from ray_tpu import train as _train
        from ray_tpu.train import Checkpoint as _Ckpt

        sess = _train.get_session()
        start = sess.get_checkpoint()
        if start is not None:
            state = start.as_pytree()
            w = float(jnp.asarray(state["w"])[0])
            start_step = int(state["step"]) + 1
        else:
            w, start_step = 0.0, 0
        for step in range(start_step, config["steps"]):
            if sess.preemption_requested():
                break
            w += 1.0
            ckpt = None
            if sess.world_rank == 0:
                ckpt = _Ckpt.from_pytree(
                    {"w": jnp.asarray([w]), "step": jnp.asarray(step)},
                    sess.checkpoint_dir(step),
                    step=step, world_size=sess.world_size,
                )
            _train.report({"step": step, "w": w,
                           "loss": 1.0 / (w + 1.0)}, checkpoint=ckpt)
            _time.sleep(config.get("step_sleep", 0.0))

    return loop


# ---------------------------------------------------------------- helpers


def _arm(specs):
    from ray_tpu.core.runtime_context import current_runtime

    nm = current_runtime()._nm
    return nm.call_sync(nm._gcs.chaos_arm(specs), timeout=30)


def _train_events():
    from ray_tpu.util.state import list_cluster_events

    return list_cluster_events(source="TRAIN")


# ----------------------------------------------------------------- phases


def phase_rendezvous(tail):
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.init(
        num_cpus=4,
        resources={"TPU": GANG},
        system_config={"num_prestart_workers": 0,
                       "heartbeat_interval_s": 0.1},
    )
    try:
        t0 = time.monotonic()
        result = JaxTrainer(
            make_rendezvous_loop(),
            train_loop_config={},
            scaling_config=ScalingConfig(
                num_workers=GANG, use_tpu=True,
                resources_per_worker={"TPU": 1},
            ),
            run_config=RunConfig(name="chaos-rendezvous"),
        ).fit()
        elapsed = time.monotonic() - t0
        ok = (result.error is None
              and result.metrics.get("processes") == GANG
              and result.metrics.get("global_devices")
              == GANG * DEVICES_PER_PROC)
        tail.append(
            f"  rendezvous gang={GANG}x{DEVICES_PER_PROC}dev: "
            f"processes={result.metrics.get('processes')} "
            f"global_devices={result.metrics.get('global_devices')} "
            f"sharded_sum={result.metrics.get('total')} "
            f"({elapsed:.1f}s)"
            + ("" if ok else f" ERROR={result.error}")
        )
        return {
            "ok": bool(ok),
            "processes": result.metrics.get("processes"),
            "local_devices": result.metrics.get("local_devices"),
            "global_devices": result.metrics.get("global_devices"),
            "seconds": round(elapsed, 2),
            "note": "multi-process jax.distributed rendezvous via "
                    "GCS-KV-brokered coordinator; cross-process "
                    "collectives unsupported on the CPU backend "
                    "(per-process 4-device sharded step instead)",
            "error": str(result.error) if result.error else None,
        }
    finally:
        ray_tpu.shutdown()


def phase_gang_restart(tail, storage_root):
    import ray_tpu
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, \
        ScalingConfig
    from ray_tpu.util import faults
    from ray_tpu.util.metrics import get_metrics_report

    steps = 8
    ray_tpu.init(
        num_cpus=4,
        system_config={"num_prestart_workers": 0,
                       "heartbeat_interval_s": 0.1},
    )
    try:
        baseline = JaxTrainer(
            make_elastic_loop(),
            train_loop_config={"steps": steps, "step_sleep": 0.15},
            scaling_config=ScalingConfig(num_workers=GANG),
            run_config=RunConfig(
                storage_path=os.path.join(storage_root, "base")),
        ).fit()
        assert baseline.error is None, baseline.error

        rt = current_runtime()
        known = {k.split("/")[1] for k in rt.kv_keys("__train__/")
                 if len(k.split("/")) >= 2}
        holder = {}

        def run():
            holder["result"] = JaxTrainer(
                make_elastic_loop(),
                train_loop_config={"steps": steps, "step_sleep": 0.15},
                scaling_config=ScalingConfig(num_workers=GANG),
                run_config=RunConfig(
                    storage_path=os.path.join(storage_root, "chaos"),
                    failure_config=FailureConfig(max_failures=1),
                ),
            ).fit()

        t0 = time.monotonic()
        t = threading.Thread(target=run, daemon=True)
        t.start()
        run_id, deadline = None, time.time() + 30
        while run_id is None and time.time() < deadline:
            for key in rt.kv_keys("__train__/"):
                parts = key.split("/")
                if len(parts) >= 2 and parts[1] and parts[1] not in known:
                    run_id = parts[1]
                    break
            time.sleep(0.05)
        assert run_id, "train run never appeared in KV"
        _arm([{"point": "train_worker", "mode": "once", "n": 2,
               "match": {"rank": "1", "run": run_id}}])
        t.join(timeout=180)
        _arm([])
        faults.clear()
        assert not t.is_alive(), "chaotic fit never finished"
        chaotic = holder["result"]
        elapsed = time.monotonic() - t0
        match = (chaotic.error is None
                 and chaotic.metrics.get("step")
                 == baseline.metrics.get("step")
                 and chaotic.metrics.get("w") == baseline.metrics.get("w"))
        restarts = [e for e in _train_events()
                    if "restarting after failure" in e.get("message", "")]
        recoveries = [e for e in _train_events()
                      if "recovered" in e.get("message", "")]
        recovery_s = None
        if recoveries:
            recovery_s = (recoveries[-1].get("custom_fields") or {}).get(
                "recovery_seconds")
        report = get_metrics_report()
        tail.append(
            f"  gang-restart: rank1 killed mid-step (train_worker), "
            f"restarts={len(restarts)} recovery="
            f"{recovery_s if recovery_s is not None else '?'}s "
            f"final step={chaotic.metrics.get('step')} "
            f"w={chaotic.metrics.get('w')} "
            f"{'== baseline' if match else '!= baseline FAIL'}"
        )
        return {
            "ok": bool(match and restarts),
            "final_step": chaotic.metrics.get("step"),
            "final_w": chaotic.metrics.get("w"),
            "baseline_step": baseline.metrics.get("step"),
            "baseline_w": baseline.metrics.get("w"),
            "gang_restarts": len(restarts),
            "recovery_seconds": recovery_s,
            "total_seconds": round(elapsed, 2),
            "train_metrics_declared": sorted(
                k for k in report if k.startswith("ray_tpu_train_")
            ),
            "error": str(chaotic.error) if chaotic.error else None,
        }
    finally:
        ray_tpu.shutdown()


def phase_checkpoint_chaos(tail, storage_root):
    import ray_tpu
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, \
        ScalingConfig
    from ray_tpu.train.checkpoint import latest_committed
    from ray_tpu.util import faults

    storage = os.path.join(storage_root, "ckptchaos")
    ray_tpu.init(
        num_cpus=4,
        system_config={"num_prestart_workers": 0,
                       "heartbeat_interval_s": 0.1},
    )
    try:
        _arm([{"point": "checkpoint_io", "mode": "once", "n": 4,
               "match": {"op": "save"}}])
        try:
            result = JaxTrainer(
                make_elastic_loop(),
                train_loop_config={"steps": 5},
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(
                    storage_path=storage,
                    failure_config=FailureConfig(max_failures=1),
                ),
            ).fit()
        finally:
            _arm([])
            faults.clear()
        final = latest_committed(storage)
        ok = (result.error is None and result.metrics.get("step") == 4
              and final is not None and final.manifest().get("step") == 4)
        tail.append(
            f"  checkpoint-chaos: save fault at step 3, fell back to "
            f"previous commit, final committed step="
            f"{final.manifest().get('step') if final else None} "
            f"{'OK' if ok else 'FAIL'}"
        )
        return {
            "ok": bool(ok),
            "final_step": result.metrics.get("step"),
            "final_committed_step":
                final.manifest().get("step") if final else None,
            "error": str(result.error) if result.error else None,
        }
    finally:
        ray_tpu.shutdown()


def phase_rolling_restart(tail):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, \
        ScalingConfig

    steps = 24
    with Cluster(head_resources={"CPU": 2}) as cluster:
        cluster.add_node(num_cpus=4, resources={"trainer": 4})
        inner = make_elastic_loop()

        def loop(config):
            inner({"steps": 24, "step_sleep": 0.6})

        holder = {}

        def run():
            holder["result"] = JaxTrainer(
                loop,
                train_loop_config={},
                scaling_config=ScalingConfig(
                    num_workers=GANG,
                    resources_per_worker={"CPU": 1, "trainer": 1},
                ),
                run_config=RunConfig(
                    name="chaos-rolling",
                    failure_config=FailureConfig(max_failures=0),
                ),
            ).fit()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # Roll WHILE the loop is still running (~14s of steps left).
        time.sleep(5.0)
        t0 = time.monotonic()
        replaced = cluster.rolling_restart()
        roll_s = time.monotonic() - t0
        t.join(timeout=240)
        assert not t.is_alive(), "fit never finished after the roll"
        result = holder["result"]
        history = result.metrics_history or []
        steps_seen = [m["step"] for m in history]
        dupes = len(steps_seen) - len(set(steps_seen))
        preempts = [e for e in _train_events()
                    if "preempted" in e.get("message", "")]
        ok = (result.error is None
              and result.metrics.get("step") == steps - 1
              and dupes <= 1
              and bool(preempts)
              and all(m["w"] == m["step"] + 1.0 for m in history))
        tail.append(
            f"  rolling-restart under fit: {len(replaced)} node(s) "
            f"replaced in {roll_s:.1f}s, steps re-executed={dupes} "
            f"(<=1), final step={result.metrics.get('step')} "
            f"{'OK' if ok else 'FAIL'}"
        )
        return {
            "ok": bool(ok),
            "nodes_replaced": len(replaced),
            "roll_seconds": round(roll_s, 2),
            "steps_lost": dupes,
            "preemptions": len(preempts),
            "final_step": result.metrics.get("step"),
            "error": str(result.error) if result.error else None,
        }


# ------------------------------------------------------------------- main


def main() -> int:
    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        _REPO, "MULTICHIP_r06.json")
    import tempfile

    storage_root = tempfile.mkdtemp(prefix="rtpu-train-chaos-")
    tail = []
    record = {
        "gang": GANG,
        "devices_per_process": DEVICES_PER_PROC,
        "phases": {},
    }
    failures = []
    for name, fn in (
        ("rendezvous", lambda: phase_rendezvous(tail)),
        ("gang_restart", lambda: phase_gang_restart(tail, storage_root)),
        ("checkpoint_chaos",
         lambda: phase_checkpoint_chaos(tail, storage_root)),
        ("rolling_restart", lambda: phase_rolling_restart(tail)),
    ):
        try:
            record["phases"][name] = fn()
        except BaseException as e:  # noqa: BLE001 — recorded, rc != 0
            record["phases"][name] = {"ok": False, "error": repr(e)}
            tail.append(f"  {name}: EXCEPTION {e!r}")
        if not record["phases"][name].get("ok"):
            failures.append(name)
    record["ok"] = not failures
    record["rc"] = 0 if not failures else 1
    status = "OK" if not failures else f"FAILED ({', '.join(failures)})"
    tail.append(f"train_chaos(gang={GANG}x{DEVICES_PER_PROC}dev): {status}")
    record["tail"] = "\n".join(tail) + "\n"
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(record["tail"], end="")
    print(f"wrote {out_path}")
    return record["rc"]


if __name__ == "__main__":
    sys.exit(main())
