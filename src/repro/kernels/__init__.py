# Pallas kernel tier: the compute hot-spots of the model zoo.  `ops` holds
# the jit'd public wrappers (interpret mode off-TPU, compiled on a TPU);
# `ref` the pure-jnp oracles; `compat` the one mesh constructor;
# `repro.workloads.calibrate` times these kernels to produce measured
# compute windows for replay; `ops.gmm` (megablox) runs the expert-parallel
# MoE layer's local experts.
from .ops import flash_attention, gmm, grouped_matmul, rmsnorm, ssd_scan

__all__ = ["flash_attention", "gmm", "grouped_matmul", "rmsnorm", "ssd_scan"]
