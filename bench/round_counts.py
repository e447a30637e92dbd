"""Operations and bytes of the adaptive multi-round path, counted from
its shapes: the work the round semantics requires per evaluation (for
``rounds_mfu``), and the ``greedy_assign`` kernel's own operations and
bytes (for ``greedy_assign_roofline``)."""
from __future__ import annotations

import math

from bench.counters import round_ops_per_eval


def _select(L: int) -> int:
    """An order statistic (or a sort) of L values, at L*ceil(log2 L)
    comparisons."""
    return L * max(1, math.ceil(math.log2(L)))


def rounds_ops_per_eval(scheme: dict, n: int) -> int:
    """Arithmetic one (trial, scheme, round) evaluation requires under
    the semantics of ``bench/refs/adaptive_round.py``, whatever implements
    it.  Drawing the delays and stepping the regime chain are not counted.

    - static cs / lb: as a single round (``bench.counters``);
    - adaptive: the round of its base, plus the greedy (sorting the n
      estimates; n picks, each scoring the n rows over their r slots with
      a multiply-add, taking the least and adding the r weights of the
      pick) and the censored update (per slot a compare, a masked add and
      a count; per worker a divide and the EMA's four operations);
    - re-balancing adds the n*(cap-1) slot costs, the order statistic
      among them, the count per worker and the n*cap load masks.
    """
    fam, r = scheme["family"], int(scheme["r"])
    ops = round_ops_per_eval(fam, n, r)
    if not scheme.get("adaptive"):
        return ops
    ops += _select(n) + n * (2 * n * r + n + 2 * r)
    ops += 3 * n * r + 5 * n
    if scheme.get("rebalance"):
        slots = n * (r - 1)
        ops += 2 * slots + _select(slots) + n * r
    return ops


def greedy_kernel_ops(n: int) -> int:
    """Operations of one trial's pick loop as the ``greedy_assign`` kernel
    computes them on its n lanes (the lanes that pad a vector register
    are not counted): n picks, each the fixed-order score sum (n products
    and n - 1 sums a row), the exact select of the picked weight row (n - 1
    steps of a compare and an n-lane select), and 22 n-lane masks,
    minima, selects, sums and the cover update."""
    return n * (n * (2 * n - 1) + (n - 1) * (n + 1) + 22 * n)


def greedy_kernel_bytes(n: int, rows: int) -> int:
    """Bytes one ``greedy_assign`` call over ``rows`` trials moves: the
    picker order, the reciprocal estimates, the reissue priorities in and
    the worker of each row out (4 bytes each a lane), and the (n, n)
    weights and their transpose once."""
    return 16 * n * rows + 8 * n * n
