"""What the routed-expert kernel (ops/moe.py) HAS to do, from the routing
the program counted: operations and bytes the algorithm needs, never what
an implementation happens to spend (kernel_cost.py's rule).

A (token, expert) pair is three matrix products of one row: gate and up
[d] x [d, f], down [f] x [f, d]: 6 * d * f operations. An expert that has
at least one pair in a step and layer must be read once there: its three
matrices, 3 * d * f elements; a pair reads its row and writes its result
(2 * d elements). bf16. Not counted: the rows that pad a group to whole
tiles, a second read of an expert whose group spans several tiles, the
router, the gather and the combine around the kernel.
"""

from __future__ import annotations

from typing import Dict, Tuple


def moe_experts_work(pairs: float, hits: float, dims: Dict
                     ) -> Tuple[float, float]:
    """(flops, bytes) for `pairs` routed (token, expert) pairs over `hits`
    distinct (step, layer, expert) with at least one pair."""
    d, f, eb = dims["hidden"], dims["ffn"], 2
    flops = 6.0 * d * f * pairs
    nbytes = (3.0 * d * f * hits + 2.0 * d * pairs) * eb
    return flops, nbytes
