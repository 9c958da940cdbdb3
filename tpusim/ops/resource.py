"""Resource algebra kernels: fit tests, placement (Sub), eviction (Add).

Re-implements pkg/type/resource.go:454-531 (Sub/Add), frag.go:447-458
(CanNodeHostPodOnGpuMemory), utils.go:950-1005 (IsNodeAccessibleToPod) and
cache/gpunodeinfo.go:136-204 (AllocateGpuId) as shape-static JAX functions
over a single node's device vector `gpu_left: i32[8]`; everything vmaps over
the node axis. 0-milli padding slots never fit a >0 request, so no explicit
device-count masking is needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_GPUS_PER_NODE, MILLI


def is_accessible(node_gpu_type, pod_gpu_mask):
    """GPU-model constraint check (ref: utils.go:957-1005).

    pod_gpu_mask == 0 (no constraint) → accessible anywhere, including
    CPU-only nodes. Otherwise the node's model bit must be set; CPU-only
    nodes (gpu_type == -1) match nothing.
    """
    node_bit = jnp.where(
        node_gpu_type >= 0, jnp.int32(1) << node_gpu_type.astype(jnp.int32), 0
    )
    return (pod_gpu_mask == 0) | ((pod_gpu_mask & node_bit) != 0)


def first_max(x):
    """(value, index) of the first maximum of a vector: what
    `i = argmax(x); x[i], i` gives, from reductions alone. Indexing the
    vector with the index just computed is a gather of one of eight for
    every (lane, type) once the kernels are vmapped, and the chip runs it
    serialized (PERF.md section 6, PR 35). Ties take the first index, as
    argmax does and fgd_score.go:111-134 asks; the value is the same on
    every tied slot. The callers' vectors hold no NaN, so `max` and the
    element at `argmax` are one number: `int32` device scores (FGD, PWR),
    and DotProd's `float32` slots, each finite or `_NEG` (-inf)."""
    return x.max(), jnp.argmax(x).astype(jnp.int32)


def can_host_on_gpu(gpu_left, pod_gpu_milli, pod_gpu_num):
    """True if >= gpu_num devices each have >= gpu_milli free
    (ref: frag.go:447-458). Only meaningful for pod_gpu_milli > 0."""
    fit = (gpu_left >= pod_gpu_milli) & (pod_gpu_milli > 0)
    return fit.sum() >= pod_gpu_num


def gpu_frag_milli(gpu_left, pod_gpu_milli):
    """Total free milli on devices individually too small for the pod
    (ref: frag.go:205-213 GetGpuFragMilliByNodeResAndPodRes)."""
    return jnp.where(gpu_left < pod_gpu_milli, gpu_left, 0).sum()


def can_allocate(gpu_left, pod_gpu_milli, pod_gpu_num):
    """Feasibility of the Filter-phase AllocateGpuId two-pointer packer
    (ref: gpunodeinfo.go:169-201).

    The greedy pointer consumes floor(left/milli) request-units per device
    before advancing, so feasibility is exactly
    sum_d floor(left_d / milli) >= gpu_num. (For whole-GPU pods, milli==1000,
    this degenerates to can_host_on_gpu; trace pods with gpu_num > 1 always
    request milli == 1000 — pod.go:111-123 panics otherwise.)
    """
    units = jnp.where(pod_gpu_milli > 0, gpu_left // jnp.maximum(pod_gpu_milli, 1), 0)
    return units.sum() >= pod_gpu_num


def _stable_asc_order(gpu_left):
    """Ascending stable order of device indices (ref: resource.go:179-197)."""
    return jnp.argsort(gpu_left, stable=True)


def select_devices_packed(gpu_left, pod_gpu_milli, pod_gpu_num):
    """Sub's device choice: take gpu_num fitting devices, least-free first,
    ties by device index (ref: resource.go:454-480).

    Returns (dev_mask: bool[8], ok: bool).
    """
    order = _stable_asc_order(gpu_left)
    fit_sorted = (gpu_left[order] >= pod_gpu_milli) & (pod_gpu_milli > 0)
    take_sorted = fit_sorted & (jnp.cumsum(fit_sorted) <= pod_gpu_num)
    dev_mask = jnp.zeros_like(fit_sorted).at[order].set(take_sorted)
    ok = take_sorted.sum() >= pod_gpu_num
    return dev_mask, ok


def sub_devices(gpu_left, pod_gpu_milli, pod_gpu_num):
    """The device half of Sub: what the node's device vector reads after a
    (gpu_milli, gpu_num) request took its devices. It depends on the pod
    through that pair alone, so callers that try many pods on one node try
    it once a distinct request (policies/fgd.py).

    Returns (gpu_left', dev_mask: bool[8], ok)."""
    dev_mask, gpu_ok = select_devices_packed(gpu_left, pod_gpu_milli, pod_gpu_num)
    new_gpu = gpu_left - dev_mask.astype(jnp.int32) * pod_gpu_milli
    return (
        jnp.where(pod_gpu_num > 0, new_gpu, gpu_left),
        dev_mask & (pod_gpu_num > 0),
        (pod_gpu_num == 0) | gpu_ok,
    )


def sub_pod(cpu_left, mem_left, gpu_left, pod):
    """Schedule the pod onto the node (ref: resource.go:454-480 Sub).

    Returns (cpu_left', mem_left', gpu_left', dev_mask, ok). On ok == False
    the returned state must be discarded by the caller (Go returns an error).
    Note Sub itself does not check memory; the scheduler's Filter does.
    """
    new_gpu, dev_mask, gpu_ok = sub_devices(gpu_left, pod.gpu_milli, pod.gpu_num)
    return (
        cpu_left - pod.cpu,
        mem_left - pod.mem,
        new_gpu,
        dev_mask,
        (cpu_left >= pod.cpu) & gpu_ok,
    )


def add_pod(cpu_left, mem_left, gpu_left, pod, dev_mask):
    """Evict the pod, returning its resources to the known devices
    (ref: resource.go:482-531 Add with a valid gpu-index list)."""
    return (
        cpu_left + pod.cpu,
        mem_left + pod.mem,
        gpu_left + dev_mask.astype(jnp.int32) * pod.gpu_milli,
    )


def allocate_exclusive(gpu_left, pod_total_milli):
    """First fully-free devices, in index order, until the whole-GPU request
    is covered (ref: resource.go:383-403 AllocateExclusiveGpuId).

    Returns a bool[8] device mask (empty if not enough idle devices).
    """
    free = gpu_left == MILLI
    need = (pod_total_milli + MILLI - 1) // MILLI
    take = free & (jnp.cumsum(free) <= need)
    enough = free.sum() * MILLI >= pod_total_milli
    return take & enough


def allocate_two_pointer(gpu_left, pod_gpu_milli, pod_gpu_num):
    """Reserve-phase AllocateGpuId for multi-GPU pods
    (ref: gpunodeinfo.go:182-201): walk devices in index order, taking
    floor(left/milli) request-units from each until gpu_num are packed.

    Returns (per-device unit counts i32[8], ok). With milli == 1000 (always
    true for trace multi-GPU pods) the counts are a 0/1 mask of the first
    gpu_num fully-fitting devices.
    """
    units = jnp.where(pod_gpu_milli > 0, gpu_left // jnp.maximum(pod_gpu_milli, 1), 0)
    cum = jnp.cumsum(units)
    prev = cum - units
    take = jnp.clip(pod_gpu_num - prev, 0, units)
    ok = cum[-1] >= pod_gpu_num
    return take, ok


def allocate_share_best(gpu_left, pod_gpu_milli):
    """Tightest-fit device for a share-GPU pod (ref: open_gpu_share.go:285-304
    allocateGpuIdBasedOnBestFit, and gpunodeinfo.go:169-181): min free milli
    among fitting devices, first index on ties. Returns device id or -1."""
    fits = gpu_left >= pod_gpu_milli
    key = jnp.where(fits, gpu_left, jnp.iinfo(jnp.int32).max)
    dev = jnp.argmin(key)  # argmin takes the first index on ties
    return jnp.where(fits.any(), dev, -1).astype(jnp.int32)


def allocate_share_worst(gpu_left, pod_gpu_milli):
    """Loosest-fit device (ref: open_gpu_share.go:306-325): max free milli
    among fitting devices, first index on ties."""
    fits = gpu_left >= pod_gpu_milli
    key = jnp.where(fits, gpu_left, jnp.iinfo(jnp.int32).min)
    dev = jnp.argmax(key)
    return jnp.where(fits.any(), dev, -1).astype(jnp.int32)


def allocate_share_random(gpu_left, pod_gpu_milli, key):
    """Uniform-random fitting device (ref: open_gpu_share.go:327-343
    reservoir sampling == uniform choice)."""
    fits = gpu_left >= pod_gpu_milli
    n = fits.sum()
    u = jax.random.uniform(key, (MAX_GPUS_PER_NODE,))
    score = jnp.where(fits, u, -1.0)
    dev = jnp.argmax(score)
    return jnp.where(n > 0, dev, -1).astype(jnp.int32)


def flatten_gpu_left(gpu_left):
    """Canonical dedup/memo key: devices sorted descending, padded to 8
    (ref: resource.go:199-215 Flatten)."""
    return -jnp.sort(-gpu_left)
