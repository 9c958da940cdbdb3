"""Array-of-structs → struct-of-arrays domain model.

The reference keeps cluster state as a graph of k8s objects + annotations
(pkg/type/resource.go:51-72 NodeResource/PodResource; the fake API server).
Here the whole cluster is a handful of dense integer arrays, padded to
MAX_GPUS_PER_NODE devices per node, so that every policy/frag kernel is a
shape-static vmap over the node axis and the event loop is a lax.scan.

All resource quantities are int32 milli-units (CPU milli, GPU milli, MiB for
memory) — feasibility tests are exact integer comparisons, matching the
reference's int64 semantics (SURVEY.md §7.3 "Exact integer semantics").
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from tpusim.constants import MAX_GPUS_PER_NODE, MILLI


class NodeState(NamedTuple):
    """Cluster node state, one row per node (ref: NodeResource, resource.go:61-72).

    gpu_left rows are padded with 0 beyond gpu_cnt devices; 0-milli pads are
    inert in every kernel (a pod's per-GPU request is >0 whenever GPU math
    runs, so pads never fit, never count as fully-free capacity, and add 0 to
    totals). A sweep's allocation ratio sums whole rows on that ground
    (driver._slice_sweep_lanes); tests/test_sweep_slice.py holds the pads at
    0 in the loaders' initial state and in every lane's final state, on every
    body of the step and under fault plans.
    """

    cpu_left: jnp.ndarray  # i32[N] milli-CPU free
    cpu_cap: jnp.ndarray  # i32[N] milli-CPU allocatable
    mem_left: jnp.ndarray  # i32[N] MiB free
    mem_cap: jnp.ndarray  # i32[N] MiB allocatable
    gpu_left: jnp.ndarray  # i32[N, 8] milli-GPU free per device
    gpu_cnt: jnp.ndarray  # i32[N] number of physical GPUs
    gpu_type: jnp.ndarray  # i32[N] GPU model id, -1 = no GPU
    cpu_type: jnp.ndarray  # i32[N] CPU model id (0 = unknown profile)
    aff_cnt: jnp.ndarray  # i32[N, 9] pods per GPU-affinity class (GpuClustering)

    @property
    def num_nodes(self) -> int:
        return self.cpu_left.shape[0]

    def total_gpu_left(self) -> jnp.ndarray:
        """Per-node total idle milli-GPU (ref: resource.go:163-168)."""
        return self.gpu_left.sum(axis=-1)

    def fully_free_gpus(self) -> jnp.ndarray:
        """Per-node count of completely idle devices (ref: resource.go:170-177)."""
        return (self.gpu_left == MILLI).sum(axis=-1)


# The leaves of a NodeState that NO step of any engine writes, fault steps
# included (a bind, a release, an eviction, a node's loss and return move
# the *_left leaves and aff_cnt alone): the cluster's own capacities and
# models, 20 of a node's 96 bytes. A sweep's lanes share them, so its program
# gives them no lane axis (driver._sweep_engine; tests/test_sweep_shared.py
# holds every body to it by value).
CAPACITY_LEAVES = ("cpu_cap", "mem_cap", "gpu_cnt", "gpu_type", "cpu_type")


def make_node_state(
    cpu_cap,
    mem_cap,
    gpu_cnt,
    gpu_type,
    cpu_type=None,
) -> NodeState:
    """Build an all-idle NodeState from per-node capacity arrays."""
    cpu_cap = np.asarray(cpu_cap, np.int32)
    n = cpu_cap.shape[0]
    mem_cap = np.asarray(mem_cap, np.int32)
    gpu_cnt = np.asarray(gpu_cnt, np.int32)
    gpu_type = np.asarray(gpu_type, np.int32)
    cpu_type = (
        np.zeros(n, np.int32) if cpu_type is None else np.asarray(cpu_type, np.int32)
    )
    gpu_left = (np.arange(MAX_GPUS_PER_NODE)[None, :] < gpu_cnt[:, None]).astype(
        np.int32
    ) * MILLI
    return NodeState(
        cpu_left=jnp.asarray(cpu_cap),
        cpu_cap=jnp.asarray(cpu_cap),
        mem_left=jnp.asarray(mem_cap),
        mem_cap=jnp.asarray(mem_cap),
        gpu_left=jnp.asarray(gpu_left),
        gpu_cnt=jnp.asarray(gpu_cnt),
        gpu_type=jnp.asarray(gpu_type),
        cpu_type=jnp.asarray(cpu_type),
        aff_cnt=jnp.zeros((n, 9), jnp.int32),
    )


class PodSpec(NamedTuple):
    """Pod resource request (ref: PodResource, resource.go:51-58).

    Scalar fields for a single pod, or [P] arrays for a batch. gpu_milli is
    the per-device request (0-1000); gpu_mask is the allowed-GPU-model bitmask
    (0 = no constraint, ref: data/README.md gpu_spec).
    """

    cpu: jnp.ndarray  # i32 milli-CPU request
    mem: jnp.ndarray  # i32 MiB request
    gpu_milli: jnp.ndarray  # i32 per-GPU milli request
    gpu_num: jnp.ndarray  # i32 number of GPUs
    gpu_mask: jnp.ndarray  # i32 allowed GPU model bitmask
    pinned: jnp.ndarray  # i32 nodeSelector-pinned node index, -1 = free

    def total_gpu_milli(self):
        """ref: resource.go:129-131 TotalMilliGpu."""
        return self.gpu_milli * self.gpu_num

    def is_gpu_share(self):
        """ref: resource.go:405-411 IsGpuShare."""
        return (self.gpu_num == 1) & (self.gpu_milli < MILLI)


def make_pod(cpu=0, mem=0, gpu_milli=0, gpu_num=0, gpu_mask=0, pinned=-1) -> PodSpec:
    return PodSpec(
        cpu=jnp.int32(cpu),
        mem=jnp.int32(mem),
        gpu_milli=jnp.int32(gpu_milli),
        gpu_num=jnp.int32(gpu_num),
        gpu_mask=jnp.int32(gpu_mask),
        pinned=jnp.int32(pinned),
    )


class TypicalPods(NamedTuple):
    """Target-workload distribution for the frag math (ref: frag.go:285-380).

    Fixed-size [T] arrays, padded with freq == 0 rows (pads contribute nothing
    to any weighted sum).
    """

    cpu: jnp.ndarray  # i32[T]
    gpu_milli: jnp.ndarray  # i32[T]
    gpu_num: jnp.ndarray  # i32[T]
    gpu_mask: jnp.ndarray  # i32[T]
    freq: jnp.ndarray  # f32[T], sums to 1

    @property
    def size(self) -> int:
        return self.cpu.shape[0]


def make_typical_pods(rows) -> TypicalPods:
    """rows: iterable of (cpu_milli, gpu_milli, gpu_num, gpu_mask, freq)."""
    rows = list(rows)
    cpu, milli, num, mask, freq = (
        zip(*rows) if rows else ((), (), (), (), ())
    )
    return TypicalPods(
        cpu=jnp.asarray(np.array(cpu, np.int32)),
        gpu_milli=jnp.asarray(np.array(milli, np.int32)),
        gpu_num=jnp.asarray(np.array(num, np.int32)),
        gpu_mask=jnp.asarray(np.array(mask, np.int32)),
        freq=jnp.asarray(np.array(freq, np.float32)),
    )


def node_row(state: NodeState, i) -> NodeState:
    """View of one node as a NodeState of scalars (for single-node kernels)."""
    return NodeState(*(x[i] for x in state))
