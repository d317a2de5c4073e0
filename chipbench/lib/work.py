"""Least work of the RF-TCA statistics pass, counted from its shapes alone.

The statistics pass turns X (p, n) into the centred Gram G_H (2N, 2N) and the
moment u (2N,).  Whatever implements it (a Pallas kernel, the XLA scan, a
fused draw), it has to

- project every sample once: Omega X, 2 N p n FLOPs;
- accumulate the upper triangle of the (2N)^2 Gram: (2N)^2 n FLOPs
  (half of the 2 (2N)^2 n of a full product);
- read X once and write the Gram once: 4 (p n + (2N)^2) bytes in float32.

cos/sin, the moment and the centring are lower order and not counted, so the
count is a floor and the share of the roofline it gives cannot pass 100%.
"""
from __future__ import annotations


def stats_pass_work(n: int, p: int, n_features: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one statistics pass over n samples of width p."""
    two_n = 2 * n_features
    flops = 2.0 * n_features * p * n + float(two_n) ** 2 * n
    nbytes = 4.0 * (p * n + two_n**2)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least seconds on the chip and which roof bounds it ("compute"/"memory")."""
    t_compute = flops / float(peaks["bf16_flops_per_s"])
    t_memory = nbytes / float(peaks["hbm_bytes_per_s"])
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
