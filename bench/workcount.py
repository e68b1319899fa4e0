"""The work a window's schedule needs, counted from shapes alone.

The strict record of one window of W tasks, each with nr read ids and
nw write ids (-1 slots included: the shapes are what a kernel sees):

  conflict   every pair j < i, W(W-1)/2 of them, compares
             flow   nw_j x nr_i  (a write of j among the reads of i)
             output nw_j x nw_i
             anti   nw_i x nr_j
             = 2·nr·nw + nw² id compares a pair
  levels     one relaxation (max over an earlier conflicting level) a pair

With cross-window overlap each window also meets the previous one's
undrained tail: a full W x W block of pairs through the same compares,
one relaxation a pair for the carry frontier, and the floored levels in
place of the plain ones.

Bytes are the least any schedule must move: the footprints in, the
levels out (and with overlap the previous window's footprints and
levels in). Both counts describe the work, not an implementation, so a
fused or rewritten kernel reads the same.
"""
from __future__ import annotations

ID_BYTES = 4


def pair_compares(nr: int, nw: int) -> int:
    return 2 * nr * nw + nw * nw


def schedule_work(window: int, nr: int, nw: int, overlap: bool) -> dict:
    """``{"compares", "relaxations", "bytes"}`` of one window's schedule."""
    w = int(window)
    pairs = w * (w - 1) // 2
    compares = pairs * pair_compares(nr, nw)
    relax = pairs
    nbytes = w * (nr + nw) * ID_BYTES + w * ID_BYTES
    if overlap:
        compares += w * w * pair_compares(nr, nw)
        relax += w * w
        nbytes += w * (nr + nw) * ID_BYTES + w * ID_BYTES
    return {"compares": compares, "relaxations": relax, "bytes": nbytes}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str] | None:
    """(seconds, bound) the chip needs at least for ``work``: compares and
    relaxations at the calibrated VPU compare rate, or the bytes at HBM
    bandwidth, whichever is longer. None without a calibrated rate."""
    rate = peaks.get("vpu_int32_compare_per_s")
    bw = peaks.get("hbm_bytes_per_s")
    if not rate or not bw:
        return None
    t_ops = (work["compares"] + work["relaxations"]) / rate
    t_mem = work["bytes"] / bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
