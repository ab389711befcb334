"""Block-size optimization (GRIM §5.1, Listing 1).

The paper's decoupling: block size is chosen by *latency alone* (synthesized
random weights at the target pruning rate — "the pruning ratio rather than
the specific location of non-zero weights impacts the latency"), independent
of training. Accuracy then prefers the smallest block size that meets the
latency threshold.

Two `run_layer` backends:
  * ``analytic_tpu_latency`` — roofline + per-grid-step overhead model of the
    TPU v5e Pallas kernel (default on this CPU-only box; the shape of the
    curve reproduces paper Fig. 10).
  * ``wallclock_cpu_runner`` — times the jitted packed matmul on the host,
    demonstrating the paper's measured-latency mechanism end-to-end.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bcr import BCRSpec, choose_block_shape

# TPU v5e single-chip model constants for the analytic fitness: published
# peaks (Google Cloud, "TPU v5e") and an assumed grid-step cost. They steer
# block/plan choice only; none of them is a measured rate.
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
GRID_STEP_OVERHEAD = 2e-7    # assumed per-grid-step issue cost
VMEM_BYTES = 128 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class SynthLayer:
    """A synthesized GEMM layer: y[M,N] = x[M,K] @ W.T, W (N,K) BCR-pruned."""

    m: int
    k: int
    n: int
    keep_frac: float
    block_shape: Tuple[int, int]


def synthesize(m: int, k: int, n: int, keep_frac: float,
               block_shape: Tuple[int, int]) -> SynthLayer:
    """Paper Listing 1 `synthesize`: weights are random — only the rate and
    block structure matter for latency."""
    return SynthLayer(m=m, k=k, n=n, keep_frac=keep_frac, block_shape=block_shape)


def analytic_tpu_latency(layer: SynthLayer) -> float:
    """Roofline latency of the TBCRC decode kernel for this layer (seconds)."""
    br, bc = layer.block_shape
    nb_r, nb_c = layer.n // br, layer.k // bc
    import math
    rf = cf = math.sqrt(layer.keep_frac)
    r_keep = max(8, int(round(rf * br / 8)) * 8)
    c_keep = max(8, int(round(cf * bc / 8)) * 8)
    weight_bytes = nb_r * nb_c * (r_keep * c_keep * 2 + (r_keep + c_keep) * 4)
    act_bytes = layer.m * layer.k * 2 + layer.m * layer.n * 2
    # core matmul + one-hot gather/scatter flops
    flops = 2 * layer.m * nb_r * nb_c * (
        c_keep * r_keep + bc * c_keep + r_keep * br
    )
    t_mem = (weight_bytes + act_bytes) / HBM_BW
    t_compute = flops / PEAK_FLOPS
    # grid-step issue cost overlaps with double-buffered DMA: the kernel is
    # limited by whichever pipe saturates (reproduces paper Fig. 10's
    # flat-then-rising latency curve as blocks shrink)
    t_overhead = nb_r * nb_c * GRID_STEP_OVERHEAD
    return max(t_mem, t_compute, t_overhead)


def wallclock_cpu_runner(layer: SynthLayer, iters: int = 5) -> float:
    """Measured latency of the jnp packed matmul on the host CPU (seconds)."""
    from repro.core.bcrc import tbcrc_pack
    from repro.kernels.ref import bcr_spmm_ref

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (layer.n, layer.k), jnp.float32)
    spec = BCRSpec(block_shape=layer.block_shape, keep_frac=layer.keep_frac)
    packed = tbcrc_pack(w, spec)
    x = jax.random.normal(key, (layer.m, layer.k), jnp.float32)
    fn = jax.jit(bcr_spmm_ref)
    fn(x, packed).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(x, packed).block_until_ready()
    return (time.perf_counter() - t0) / iters


def wallclock_plan_fitness(m: int, k: int, n: int,
                           block_shape: Tuple[int, int], r_keep: int,
                           c_keep: int, *, impl: str = "ref",
                           iters: int = 3) -> Callable[[dict], float]:
    """Measured-latency fitness for the §4.5 plan tuner (opt-in backend —
    ``tuner.plan_cost_model``'s analytic roofline stays the default).

    Extends ``wallclock_cpu_runner``'s mechanism to the dispatch genome: a
    packed weight with EXACTLY this geometry — (nb_r, nb_c, r_keep,
    c_keep) vals, arange index planes; per §5.1 only the rate matters for
    latency, not which weights survive — is synthesized once, then each
    genome is applied via ``attach_plan``/``pack_group`` and the jitted
    matmul is timed on the host. ``impl`` must be the path serving will
    actually dispatch (``launch.serve --plan-fitness`` wires
    ``cfg.kernel_impl`` through) — timing a different impl would rank
    knobs by noise. Genomes whose ``m_tile`` cannot tile the padded batch
    score ``inf``.
    """
    from repro.core.bcrc import TBCRC

    br, bc = block_shape
    nb_r, nb_c = n // br, k // bc
    key = jax.random.PRNGKey(0)
    vals = jax.random.normal(key, (nb_r, nb_c, r_keep, c_keep), jnp.float32)
    row_idx = jnp.broadcast_to(jnp.arange(r_keep, dtype=jnp.int32),
                               (nb_r, nb_c, r_keep))
    col_idx = jnp.broadcast_to(jnp.arange(c_keep, dtype=jnp.int32),
                               (nb_r, nb_c, c_keep))
    packed = TBCRC(vals=vals, row_idx=row_idx, col_idx=col_idx,
                   shape=(n, k), block_shape=block_shape)
    x = jax.random.normal(key, (m, k), jnp.float32)

    def fitness(genome: dict) -> float:
        from repro.kernels.ops import bcr_matmul, bcr_matmul_grouped
        from repro.kernels.plan import attach_plan, pack_group

        mt = int(genome.get("m_tile", 8) or 8)
        if mt <= 0 or mt % 8:
            return float("inf")   # same legality rule as plan_cost_model
        grp = int(genome.get("group_size", 1))
        try:
            if grp > 1:
                grouped = pack_group([packed] * grp, genome)
                fn = jax.jit(lambda a: bcr_matmul_grouped(
                    a, grouped, impl=impl))
            else:
                planned = attach_plan(packed, genome)
                fn = jax.jit(lambda a: bcr_matmul(a, planned, impl=impl))
            fn(x).block_until_ready()
        except Exception:
            return float("inf")     # illegal genome for this shape
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(x).block_until_ready()
        return (time.perf_counter() - t0) / iters / grp

    return fitness


def find_opt_blk(
    m: int, k: int, n: int, keep_frac: float,
    block_sizes: Sequence[Tuple[int, int]],
    run_layer: Callable[[SynthLayer], float] = analytic_tpu_latency,
    threshold: float = 1.10,
) -> Tuple[Tuple[int, int], List[Tuple[Tuple[int, int], float]]]:
    """Paper Listing 1 `find_opt_blk`.

    Iterates candidate block sizes from smallest (most accurate) upward and
    returns the smallest one whose latency is within ``threshold`` × the best
    latency seen over the sweep; also returns the full (size, latency) log.
    """
    log: List[Tuple[Tuple[int, int], float]] = []
    for size in block_sizes:
        if n % size[0] or k % size[1]:
            continue
        layer = synthesize(m, k, n, keep_frac, size)
        log.append((size, run_layer(layer)))
    if not log:
        raise ValueError("no candidate block size divides the layer dims")
    best_latency = min(t for _, t in log)
    # smallest block size (most flexibility/accuracy) meeting the threshold
    ordered = sorted(log, key=lambda e: e[0][0] * e[0][1])
    for size, lat in ordered:
        if lat <= threshold * best_latency:
            return size, log
    return min(log, key=lambda e: e[1])[0], log


def default_candidates(n: int, k: int) -> List[Tuple[int, int]]:
    cands = []
    for br in (32, 64, 128, 256, 512):
        for bc in (128, 256, 512):
            if n % br == 0 and k % bc == 0:
                cands.append((br, bc))
    return cands or [choose_block_shape((n, k))]
