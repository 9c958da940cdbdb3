"""Node power model (ref: pkg/type/resource.go:533-563 GetEnergyConsumptionNode
and open-gpu-share/utils/const.go:48-121 energy tables).

GPU power: fully-idle devices draw idle watts, every other device draws full
watts (even minimally-used ones). CPU power: 2 vCPUs per physical core;
whole CPU packages flip from idle to full wattage as cores become busy.
"""

from __future__ import annotations

import jax.numpy as jnp

from tpusim.constants import (
    CPU_FULL_W,
    CPU_IDLE_W,
    CPU_NCORES,
    GPU_FULL_W,
    GPU_IDLE_W,
    MILLI,
)


def _gpu_model_watts(table, gpu_type):
    """table[gpu_type] for a GPU model id, 0.0 for -1 (no GPU): compares
    against the table's MAX_GPU_MODELS rows and one sum, in which every
    term but one is 0.0, so the value is the row's bit for bit. Indexed, a
    lookup a node stays a gather where the nodes are many ([40, 100000] in
    the sweep's post-pass: 0.03 s a table on the chip); this form fuses."""
    table = jnp.asarray(table)
    hit = gpu_type[..., None] == jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.where(hit, table, 0.0).sum(-1)


def gpu_power_watts(gpu_left, gpu_cnt, gpu_type):
    """GPU watts for one node (ref: resource.go:537-545): fully-idle devices
    draw idle watts, every other device draws full watts."""
    num_idle_gpus = (gpu_left == MILLI).sum().astype(jnp.float32)
    num_working = gpu_cnt.astype(jnp.float32) - num_idle_gpus
    idle_w = _gpu_model_watts(GPU_IDLE_W, gpu_type)
    full_w = _gpu_model_watts(GPU_FULL_W, gpu_type)
    return idle_w * num_idle_gpus + full_w * num_working


def gpu_busy_delta_watts(gpu_type):
    """Per-device watts increase when a fully-idle device becomes working."""
    return (_gpu_model_watts(GPU_FULL_W, gpu_type)
            - _gpu_model_watts(GPU_IDLE_W, gpu_type))


def cpu_package_watts(cpu_left, cpu_cap, ncores, idle_w, full_w):
    """CPU watts of one node from its model's (cores a package, idle W,
    full W) (ref: resource.go:547-559): 2 vCPUs per physical core; whole
    packages flip from idle to full wattage as cores become busy.

    The core and package counts are INTEGER divisions of the milli values.
    The reference's `ceil(cap / 1000 / 2)` in f32 is not safe under a
    compiler: XLA turns it into `cap * 0.0005f`, which reads 48.000004
    for 96,000 milli, so the ceil counted 49 cores and a fourth package on
    every 96-vCPU node (165 W for an empty one where the reference has
    45), and a TPU's own divide is a refined reciprocal."""
    per_core = 2 * MILLI  # milli vCPU a physical core
    ncores = ncores.astype(jnp.int32)
    real_cores = -(-cpu_cap.astype(jnp.int32) // per_core)
    idle_cores = cpu_left.astype(jnp.int32) // per_core
    num_cpus = -(-real_cores // ncores)
    active_cpus = -(-(real_cores - idle_cores) // ncores)
    return (idle_w * (num_cpus - active_cpus).astype(jnp.float32)
            + full_w * active_cpus.astype(jnp.float32))


def cpu_power_watts(cpu_left, cpu_cap, cpu_type):
    """CPU watts for one node (ref: resource.go:547-559), by its model's
    row of the energy tables."""
    return cpu_package_watts(
        cpu_left, cpu_cap, jnp.asarray(CPU_NCORES)[cpu_type],
        jnp.asarray(CPU_IDLE_W)[cpu_type], jnp.asarray(CPU_FULL_W)[cpu_type],
    )


def node_power(cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type):
    """Returns (cpu_watts, gpu_watts) for one node; vmap over nodes."""
    return (
        cpu_power_watts(cpu_left, cpu_cap, cpu_type),
        gpu_power_watts(gpu_left, gpu_cnt, gpu_type),
    )
