"""The work a search needs, counted from shapes and live candidates.

``candidate_budget`` is the static slot count of the fixed-shape search
(a copy of the program's cost model, so that the yardstick cannot move
with it): K^C cluster lists and K₂ᵀ term lists per query, each at its
capacity.

A scoring kernel's roofline share counts the work the search needs, not
the work one implementation does: every live candidate (a unique
document of the dispatched lists, ``SearchResult.n_candidates``) is
read once from the code plane with its 4-byte id, and scored with one
add per PQ fragment (``pq_adc``) or one multiply-add per dimension
(``sq8_dot``).  Static slots, padding and one-hot work do not count, so
a kernel that skips them reads higher, and one that does extra work
reads lower.
"""
from __future__ import annotations

ID_BYTES = 4


def candidate_budget(kc: int, k2: int, cluster_capacity: int,
                     term_capacity: int) -> int:
    """Static candidate slots per query."""
    return kc * cluster_capacity + k2 * term_capacity


def scoring_work(kernel: str, live: int, cfg: dict) -> tuple:
    """(bytes, ops, peak key) of scoring ``live`` candidates."""
    if kernel == "pq_adc":
        m = cfg["pq_m"]
        return live * (m + ID_BYTES), live * m, "bf16_flops"
    if kernel == "sq8_dot":
        h = cfg["hidden"]
        return live * (h + ID_BYTES), live * 2 * h, "int8_ops"
    raise KeyError(f"no work count for kernel {kernel!r}")


def least_seconds(kernel: str, live: int, cfg: dict, peaks: dict) -> tuple:
    """The least time the chip could score ``live`` candidates in, and
    which bound sets it ("hbm" or "compute")."""
    nbytes, ops, peak = scoring_work(kernel, live, cfg)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks[peak]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")


def roofline_share(kernel: str, live: int, kernel_seconds: float,
                   cfg: dict, peaks: dict):
    """Percent of the roofline the kernel reached, or None when there is
    no kernel time to divide by."""
    if not kernel_seconds or live <= 0:
        return None
    least, _ = least_seconds(kernel, live, cfg, peaks)
    return 100.0 * least / kernel_seconds
