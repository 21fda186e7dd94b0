"""The bytes an access round must move, and the least time they take.

Counted from the work Ditto's design needs, never from what today's
program moves: per operation one bucket read (``assoc`` slots of
metadata), one sample-window read of metadata for each eviction (the
paper's single RDMA_READ of ``window`` contiguous slots), and one record
of ``record_bytes`` read for each GET hit and written for each SET and
each insert.  A slot is ``slot_bytes`` of the configuration: the atomic
field and the inline access metadata.  The counters do not split hits by
operation, so GET hits are counted as hits less SETs (at least that many
GETs hit); the count stays a least one.
"""

from __future__ import annotations


def access_bytes(config: dict, counts: dict) -> int:
    """Bytes the rounds behind ``counts`` (program counters: gets, sets,
    hits, misses, insert_drops, evictions) must move."""
    pool = config["pool"]
    slot = int(config["slot_bytes"])
    record = int(config["record_bytes"])
    window = pool.get("sample_window") or 4 * pool.get("n_samples", 5)
    ops = counts["gets"] + counts["sets"]
    inserts = counts["misses"] - counts["insert_drops"]
    get_hits = max(counts["hits"] - counts["sets"], 0)
    return (ops * pool["assoc"] * slot
            + counts["evictions"] * window * slot
            + (get_hits + counts["sets"] + inserts) * record)


def least_seconds(n_bytes: int, peaks: dict) -> float:
    """The least time the bytes take at the device's peak HBM bandwidth."""
    return n_bytes / float(peaks["hbm_bytes_per_s"])
