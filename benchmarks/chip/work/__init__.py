"""Useful work of one kernel launch, from its shapes.

One module per kernel, found by name.  Each defines ``TRACE_NAMES``
(the names of the kernel's device operations in a profiler trace, as
:mod:`chip.trace` reduces them) and ``work(cfg, t_lens) -> (ops, bytes)`` for one launch that
serves real requests of lengths ``t_lens``.  Only real requests at
their own length, real neurons and real inputs count: the lane padding
of the word axis and padded batch slots do not, so the count does not
depend on how the kernel is written.  A synapse costs 2 operations per
cycle (AND and accumulate, the work of an int8 multiply-accumulate), so
the compute roof is the chip's int8 peak.
"""

from __future__ import annotations


def words(n_in: int) -> int:
    """Packed uint32 words that hold ``n_in`` 1-bit inputs."""
    return -(-n_in // 32)


def roofline(ops: float, nbytes: float, seconds: float, peak: dict
             ) -> tuple[float, str] | None:
    """Share (%) of the roofline that ``ops`` and ``nbytes`` done in
    ``seconds`` of kernel time reach, and which roof bounds it (the
    larger of ops over peak ops and bytes over peak bandwidth)."""
    if seconds <= 0 or ops <= 0:
        return None
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
