"""TPU accelerator manager: chip detection, slice/ICI-topology discovery.

Ref analogue: python/ray/_private/accelerators/tpu.py:22-56 — the reference
detects TPU pods/slices from GCE metadata + env vars (``TPU_NAME``,
``TPU_WORKER_ID``, ``TPU_ACCELERATOR_TYPE``, ``TPU_WORKER_HOSTNAMES``) and
isolates chips with ``TPU_VISIBLE_CHIPS``, but stops at a flat ``"TPU"``
resource. (No per-worker chip isolation exists here yet — ROADMAP R6:
one ``tpu`` worker process owns every chip of its host.) Here slice
membership becomes *node labels* so the scheduler can
gang-place one bundle per host of a slice (ICI-topology-aware placement,
SURVEY.md §7 phase 5 — the framework's north star).

Discovery is env-var driven: on real TPU VMs the runtime populates these
variables (GKE and GCE images both export them); the single-machine test
cluster injects them per simulated node. The GCE metadata server is
deliberately not consulted — env is authoritative and testable.
"""

from __future__ import annotations

import glob
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional

_DEV = "/dev"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# One fixed, git-ignored directory in the checkout (`make clean` drops it).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# Node-label keys published by every TPU host (ref analogue: the reference's
# ray.io/accelerator-type label plus the slice fields its tpu.py discovers).
TPU_SLICE_LABEL = "ray_tpu.io/tpu-slice"
TPU_WORKER_ID_LABEL = "ray_tpu.io/tpu-worker-id"
TPU_TOPOLOGY_LABEL = "ray_tpu.io/tpu-topology"
TPU_TYPE_LABEL = "ray_tpu.io/tpu-accelerator-type"
TPU_HOSTS_LABEL = "ray_tpu.io/tpu-slice-hosts"

# Chips per host by TPU generation (ref: tpu.py:31-49 core accounting —
# v2/v3/v4/v5p hosts carry 4 chips; v5e/v6e standalone hosts carry up to 8).
_CHIPS_PER_HOST = {
    "v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8, "v5e": 8, "v6e": 8,
}


@dataclass(frozen=True)
class TpuSliceInfo:
    """One host's view of the slice it belongs to."""

    slice_name: str
    worker_id: int
    accelerator_type: str  # e.g. "v5p-16"
    topology: str  # e.g. "2x2x2"
    num_hosts: int
    chips_per_host: int

    def labels(self) -> Dict[str, str]:
        return {
            TPU_SLICE_LABEL: self.slice_name,
            TPU_WORKER_ID_LABEL: str(self.worker_id),
            TPU_TOPOLOGY_LABEL: self.topology,
            TPU_TYPE_LABEL: self.accelerator_type,
            TPU_HOSTS_LABEL: str(self.num_hosts),
        }


def local_chip_count() -> int:
    """Count local TPU chips without importing jax (device files first,
    ref analogue: accelerators/tpu.py device detection). The one chip
    detector: ``ray_tpu.init`` advertises this many ``TPU`` and slice
    discovery falls back to it. ``/dev/vfio/vfio`` is the VFIO control
    node, not a chip, hence the ``[0-9]*`` form."""
    override = os.environ.get("TPU_CHIPS_PER_HOST_OVERRIDE")
    if override:
        try:
            return int(override)
        except ValueError:
            pass
    n = len(glob.glob(os.path.join(_DEV, "accel*")))
    if n:
        return n
    return len(glob.glob(os.path.join(_DEV, "vfio", "[0-9]*")))


def require_driver_off_jax() -> None:
    """For launchers whose ``tpu`` workers need the chip: a chip belongs
    to one process, and a parent that has imported jax may hold it."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "this driver process imported jax: it would hold the chip "
            "its tpu workers need"
        )


def worker_jax_env(worker_type: str, env: Mapping[str, str]
                   ) -> Dict[str, str]:
    """JAX placement for a spawned worker, given the parent's ``env``.

    ``cpu`` workers never open the chip (it is exclusive to one
    process). ``tpu`` workers inherit an explicit ``JAX_PLATFORMS``
    (the test suite's ``cpu`` must reach them); with none set they are
    pinned to ``tpu,cpu``, so a runtime that cannot open the chip is an
    error at backend init, not a silent CPU run.

    A ``JAX_COMPILATION_CACHE_DIR`` placed from outside is inherited as
    it is. With none, a worker that may compile for the TPU gets
    :data:`DEFAULT_COMPILE_CACHE_DIR` — never a session dir, pid or temp
    name: a directory that moves between runs is never found again, and
    every fresh gang then recompiles its ~20 s step. CPU-only workers
    get none: XLA:CPU compiles here take about a second, and its loader
    logs an error-level line of machine features for every cached
    executable it reads back."""
    if worker_type == "cpu":
        out = {"JAX_PLATFORMS": "cpu"}
    else:
        out = {"JAX_PLATFORMS": env.get("JAX_PLATFORMS") or "tpu,cpu"}
    if out["JAX_PLATFORMS"] != "cpu" and not env.get(COMPILE_CACHE_ENV):
        out[COMPILE_CACHE_ENV] = DEFAULT_COMPILE_CACHE_DIR
    return out


def _generation(accelerator_type: str) -> str:
    return accelerator_type.split("-", 1)[0].lower()


def chips_per_host(accelerator_type: str) -> int:
    return _CHIPS_PER_HOST.get(_generation(accelerator_type), 4)


def slice_chip_count(accelerator_type: str) -> int:
    """Total chips in the slice. For v2-v4 and v5p the accelerator-type
    suffix counts TensorCores (2 per chip); for v5e/v6e it counts chips
    (single-core chips) — ref: accelerators/tpu.py:31-49 core accounting."""
    try:
        suffix = int(accelerator_type.split("-", 1)[1])
    except (IndexError, ValueError):
        return 0
    gen = _generation(accelerator_type)
    if gen in ("v2", "v3", "v4", "v5p"):
        return max(1, suffix // 2)
    return suffix


def slice_num_hosts(accelerator_type: str) -> int:
    chips = slice_chip_count(accelerator_type)
    per = chips_per_host(accelerator_type)
    return max(1, (chips + per - 1) // per) if chips else 1


def detect_slice() -> Optional[TpuSliceInfo]:
    """Read slice membership from the environment. Returns None off-TPU."""
    slice_name = os.environ.get("TPU_NAME") or os.environ.get(
        "RAY_TPU_SLICE_NAME"
    )
    accel = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    if not slice_name:
        return None
    worker_id = int(os.environ.get("TPU_WORKER_ID", "0") or 0)
    topology = os.environ.get("TPU_TOPOLOGY", "")
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if hostnames:
        num_hosts = len([h for h in hostnames.split(",") if h.strip()])
    elif accel:
        num_hosts = slice_num_hosts(accel)
    else:
        num_hosts = 1
    per_host = local_chip_count() or (
        chips_per_host(accel) if accel else 0
    )
    return TpuSliceInfo(
        slice_name=slice_name,
        worker_id=worker_id,
        accelerator_type=accel,
        topology=topology,
        num_hosts=num_hosts,
        chips_per_host=per_host,
    )


def node_tpu_labels() -> Dict[str, str]:
    """Labels a starting node manager publishes (empty off-TPU)."""
    info = detect_slice()
    return info.labels() if info else {}


# --------------------------------------------------------------------- slices


def list_slices(nodes: List[Dict]) -> Dict[str, List[Dict]]:
    """Group alive node views by slice name, each sorted by worker id."""
    out: Dict[str, List[Dict]] = {}
    for view in nodes:
        if view.get("state", "alive") != "alive":
            continue
        labels = view.get("labels") or {}
        name = labels.get(TPU_SLICE_LABEL)
        if name:
            out.setdefault(name, []).append(view)
    for name in out:
        out[name].sort(
            key=lambda v: int(v["labels"].get(TPU_WORKER_ID_LABEL, "0"))
        )
    return out


def tpu_slice(
    slice_name: Optional[str] = None,
    *,
    num_hosts: Optional[int] = None,
    chips_per_bundle: Optional[float] = None,
    timeout: float = 30.0,
):
    """Reserve every host of one TPU slice as a placement group — the SPMD
    gang primitive (SURVEY.md §7 phase 5: "placement group whose bundles are
    the hosts of one slice").

    Bundle *i* is pinned (via per-bundle label selectors) to the slice host
    with worker-id *i*, so actor rank order matches the slice's ICI wiring
    order. Returns the created :class:`PlacementGroup`.
    """
    from .placement_group import placement_group
    from .runtime_context import current_runtime

    rt = current_runtime()
    slices = list_slices(rt.nodes())
    if not slices:
        raise ValueError("no TPU slices registered in the cluster")
    if slice_name is None:
        # Pick the largest fully-registered slice deterministically.
        def completeness(item):
            name, hosts = item
            declared = int(
                hosts[0]["labels"].get(TPU_HOSTS_LABEL, len(hosts))
            )
            return (len(hosts) >= declared, len(hosts), name)

        slice_name = max(slices.items(), key=completeness)[0]
    hosts = slices.get(slice_name)
    if not hosts:
        raise ValueError(f"unknown TPU slice {slice_name!r}")
    declared = int(hosts[0]["labels"].get(TPU_HOSTS_LABEL, len(hosts)))
    want = num_hosts or declared
    if len(hosts) < want:
        raise ValueError(
            f"slice {slice_name!r} has {len(hosts)} registered hosts, "
            f"need {want}"
        )
    hosts = hosts[:want]
    bundles = []
    selectors = []
    for host in hosts:
        labels = host["labels"]
        chips = chips_per_bundle
        if chips is None:
            chips = host["resources_total"].get("TPU", 0) or 1
        bundles.append({"TPU": float(chips)})
        selectors.append(
            {
                TPU_SLICE_LABEL: slice_name,
                TPU_WORKER_ID_LABEL: labels.get(TPU_WORKER_ID_LABEL, "0"),
            }
        )
    pg = placement_group(
        bundles,
        strategy="STRICT_SPREAD",
        name=f"tpu-slice-{slice_name}",
        bundle_label_selectors=selectors,
    )
    if timeout and not pg.wait(timeout):
        from .placement_group import remove_placement_group

        remove_placement_group(pg)
        raise TimeoutError(
            f"TPU slice {slice_name!r} placement group not ready in "
            f"{timeout}s"
        )
    return pg
