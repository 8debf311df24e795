"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of the work the algorithm requires, not of what a compiler
emits: no recompute, no padding, float32 operands (4 bytes).  A matrix
product of (m, k) by (k, n) is 2mkn operations.  "Needed bytes" are the
operands read once and the results written once.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

F32 = 4
Shapes = Sequence[Tuple[int, int]]      # per layer (fan_in, fan_out)


def dense_fwd(m: int, k: int, n: int) -> Tuple[float, float]:
    """relu(x @ w + b): the product, the bias add and the ReLU."""
    return 2.0 * m * k * n + 2.0 * m * n, F32 * (m * k + k * n + n + m * n)


def dense_dx(m: int, k: int, n: int) -> Tuple[float, float]:
    """dx = (dy * [y > 0]) @ w.T: reads dy, y and w, writes dx."""
    return 2.0 * m * k * n + m * n, F32 * (2 * m * n + k * n + m * k)


def dense_dw(m: int, k: int, n: int) -> Tuple[float, float]:
    """dw = x.T @ g and db = sum(g): reads x, dy, y; writes dw and db."""
    return 2.0 * m * k * n + 2.0 * m * n, F32 * (m * k + 2 * m * n + k * n + n)


def n_params(shapes: Shapes) -> int:
    return sum(k * n + n for k, n in shapes)


def mlp_forward(rows: int, shapes: Shapes) -> Tuple[float, float]:
    """A whole MLP forward whose activations stay on chip: every weight
    and bias read once, the input read and the output written once."""
    flops = sum(2.0 * rows * k * n + 2.0 * rows * n for k, n in shapes)
    nbytes = F32 * (n_params(shapes) + rows * (shapes[0][0] + shapes[-1][1]))
    return flops, nbytes


def alg1_step_flops(batch: int, g: Shapes, d: Shapes) -> float:
    """Model operations of one Algorithm 1 step (matrix products only):
    G forward and backward (no input gradient at G's first layer); D
    forward once (the paper's line 6: one Sat per sample feeds both the
    critic and D's own loss); D's input gradient for the critic term; D's
    weight gradient and, below its first layer, input gradient for its
    own update."""
    mm = lambda shapes: sum(2.0 * batch * k * n for k, n in shapes)
    g_all, d_all = mm(g), mm(d)
    g_first, d_first = mm(g[:1]), mm(d[:1])
    return (g_all + g_all + (g_all - g_first)
            + d_all + d_all + d_all + (d_all - d_first))


def alg1_dense_calls(batch: int, g: Shapes, d: Shapes
                     ) -> Dict[str, List[Tuple[int, int, int]]]:
    """The fused_dense calls one Algorithm 1 step needs, by kernel:
    forward (G, D once), dx (G's layers but the first, all of D's for the
    critic, D's but the first for its update) and dw (G, and D for its
    update)."""
    rows = lambda shapes: [(batch, k, n) for k, n in shapes]
    return {
        "fwd": rows(g) + rows(d),
        "dx": rows(g[1:]) + rows(d) + rows(d[1:]),
        "dw": rows(g) + rows(d),
    }


KERNELS = {"fwd": dense_fwd, "dx": dense_dx, "dw": dense_dw}


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
