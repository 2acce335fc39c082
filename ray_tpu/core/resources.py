"""Resource model with TPU as a first-class accelerator.

Reference parity: python/ray/_private/resource_spec.py and
python/ray/_private/accelerators/tpu.py (TPU pod/slice detection at
:198, pod-type resources at :276-319). TPU chips are native schedulable
resources ("TPU"); a node belonging to a pod slice additionally carries
topology labels (pod type, slice name, worker index, chips per host) and
— on the slice's worker 0 — the "TPU-<pod_type>-head" gang resource, so
a whole slice can be claimed by scheduling one head task/actor and
fanning out over the slice's nodes (the reference's multi-host gang
idiom).
"""
from __future__ import annotations

import os
from typing import Dict, Optional

from ..util import knobs

TPU_HEAD_FMT = "TPU-{pod_type}-head"


def detect_node_resources(num_cpus: Optional[int] = None,
                          num_tpus: Optional[int] = None) -> Dict[str, float]:
    if num_cpus is None:
        num_cpus = os.cpu_count() or 1
        # The runtime itself needs headroom; still expose at least 4 virtual
        # CPU slots so task-parallel libraries (data/tune) can overlap work —
        # CPUs in ray (and here) are scheduling tokens, not pinned cores.
        num_cpus = max(num_cpus, 4)
    res: Dict[str, float] = {"CPU": float(num_cpus)}
    if num_tpus is None:
        num_tpus = _detect_tpu_chips()
    if num_tpus:
        res["TPU"] = float(num_tpus)
    res["memory"] = float(_detect_memory_bytes())
    topo = detect_tpu_topology(num_tpus)
    if topo.get("tpu-pod-type"):
        # One gang resource per slice, held by the slice's first worker:
        # scheduling {TPU-<pod>-head: 1} lands exactly one controller task
        # on each slice (ref accelerators/tpu.py:276-319).
        if int(topo.get("tpu-worker-id", "0") or 0) == 0:
            res[TPU_HEAD_FMT.format(pod_type=topo["tpu-pod-type"])] = 1.0
    return res


def detect_tpu_topology(num_chips: Optional[int] = None) -> Dict[str, str]:
    """Slice/pod topology labels from the environment.

    Mirrors the reference's TPU pod detection from TPU-VM metadata/env
    (accelerators/tpu.py:198): on a real TPU VM, the runtime publishes
    accelerator type (e.g. "v5e-8"), the slice/pod name, and this host's
    worker index within the slice. Here they come from env so a pod can
    also be modeled in tests.
    """
    labels: Dict[str, str] = {}
    pod_type = (knobs.get_raw("RAY_TPU_POD_TYPE")
                or os.environ.get("TPU_ACCELERATOR_TYPE", ""))
    if pod_type:
        labels["tpu-pod-type"] = pod_type
    slice_name = (knobs.get_raw("RAY_TPU_SLICE")
                  or os.environ.get("TPU_NAME", ""))
    if slice_name:
        labels["tpu-slice"] = slice_name
    worker_id = (knobs.get_raw("RAY_TPU_WORKER_ID")
                 or os.environ.get("TPU_WORKER_ID", ""))
    if worker_id:
        labels["tpu-worker-id"] = worker_id
    if num_chips is None:
        num_chips = _detect_tpu_chips()
    if num_chips and labels:
        labels["tpu-chips-per-host"] = str(num_chips)
    return labels


def _detect_tpu_chips() -> int:
    """Count this host's chips WITHOUT initialising a JAX backend: a chip
    belongs to one process, and the driver or node agent that counts must
    never be that process (one owner per chip, util/jaxenv.py).

    The count is the device files the TPU runtime itself opens —
    `/dev/accel<N>` (v2-v4) and the VFIO groups `/dev/vfio/<N>` (v5e and
    later). The TPU_* environment and sysfs describe the whole host even
    where this machine was handed one chip of it, so they are not used.
    A tree pinned off the TPU by JAX_PLATFORMS has no chips to schedule.
    An unreadable /dev raises: a miscount must not look like "0 chips".
    """
    env = knobs.get_int("RAY_TPU_CHIPS")
    if env is not None:   # 0 is a real override: force chipless
        return env
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    n = 0
    for directory, prefix in (("/dev", "accel"), ("/dev/vfio", "")):
        try:
            names = os.listdir(directory)
        except FileNotFoundError:
            continue   # this host has no such driver
        n += sum(1 for name in names if name.startswith(prefix)
                 and name[len(prefix):].isdigit())
    return n


def _detect_memory_bytes() -> int:
    try:
        import psutil  # noqa: PLC0415
        return int(psutil.virtual_memory().total * 0.7)
    except Exception:
        return 8 << 30


def fits(avail: Dict[str, float], req: Dict[str, float]) -> bool:
    return all(avail.get(k, 0.0) + 1e-9 >= v for k, v in req.items() if v > 0)


def acquire(avail: Dict[str, float], req: Dict[str, float]) -> None:
    for k, v in req.items():
        if v > 0:
            avail[k] = avail.get(k, 0.0) - v


def release(avail: Dict[str, float], req: Dict[str, float]) -> None:
    for k, v in req.items():
        if v > 0:
            avail[k] = avail.get(k, 0.0) + v


def normalize_task_resources(num_cpus=None, num_tpus=None, resources=None,
                             memory=None, default_cpus: float = 1.0) -> Dict[str, float]:
    req: Dict[str, float] = dict(resources or {})
    req["CPU"] = float(default_cpus if num_cpus is None else num_cpus)
    if num_tpus:
        req["TPU"] = float(num_tpus)
    if memory:
        req["memory"] = float(memory)
    return {k: v for k, v in req.items() if v > 0}
