"""The least bytes a placement step must move, from its shapes alone.

Both kernels are bound by memory: a step reads the capacity and usage
matrices (float32 [N, R]) to score every node and writes usage back.
These are floors, so that a share of the roofline cannot pass 100%: the
feasibility, affinity and spread fields a step also reads, and every
re-read by a further wave of the bulk loop, are left out.
"""
F32 = 4


def bulk_eval_bytes(rows: int, dims: int) -> int:
    """One chained bulk eval: read capacity and usage, write usage
    (at least one wave)."""
    return 3 * rows * dims * F32


def scan_slot_bytes(rows: int, dims: int) -> int:
    """One slot of the chained scan: read capacity and usage (the
    one-row usage update is not counted)."""
    return 2 * rows * dims * F32
