"""Device kernels: the scheduler hot path as dense JAX ops.

Replaces the reference's per-node lazy iterator chain
(scheduler/rank.go:193-551 BinPackIterator, scheduler/feasible.go checkers,
scheduler/select.go Limit/MaxScore) with batched fixed-shape kernels:

- fit.py        vectorized AllocsFit + BestFit-v3 scoring over the node axis
- place.py      the placement engine: a loop over an eval's placement slots,
                up to its last active one, with a proposed-usage carry,
                scoring every node at every step
- constraints.py device-side constraint-program evaluation over hashed
                attribute code matrices (host numpy twin lives in
                scheduler/feasible.py)
- preempt.py    masked greedy preemption selection (lax.while_loop)
"""

from nomad_tpu.ops.fit import (
    fits_after,
    free_fractions,
    score_fit,
    validate_capacity,
)
from nomad_tpu.ops.place import PlaceResult, place_eval, place_eval_jit

__all__ = [k for k in dir() if not k.startswith("_")]
