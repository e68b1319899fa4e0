"""The schedule's work count against a brute-force count of the strict
record's compares, pair by pair and slot by slot."""
import itertools

import pytest

from bench import workcount


def brute(w: int, nr: int, nw: int, overlap: bool) -> dict:
    compares = relax = 0
    pairs = [(i, j) for i in range(w) for j in range(i)]
    if overlap:  # the next window's tasks against the previous window's
        pairs += list(itertools.product(range(w), range(w)))
    for _ in pairs:
        for _a, _c in itertools.product(range(nw), range(nr)):   # flow
            compares += 1
        for _a, _c in itertools.product(range(nw), range(nw)):   # output
            compares += 1
        for _a, _c in itertools.product(range(nw), range(nr)):   # anti
            compares += 1
        relax += 1
    nbytes = (w * (nr + nw) + w) * 4 * (2 if overlap else 1)
    return {"compares": compares, "relaxations": relax, "bytes": nbytes}


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("w,nr,nw", [(1, 3, 1), (5, 10, 1), (8, 2, 1),
                                     (7, 4, 2)])
def test_matches_brute_force(w, nr, nw, overlap):
    assert workcount.schedule_work(w, nr, nw, overlap) == brute(
        w, nr, nw, overlap)


def test_least_seconds_takes_the_longer_bound():
    work = {"compares": 10**9, "relaxations": 0, "bytes": 10**6}
    assert workcount.least_seconds(work, {"hbm_bytes_per_s": 1e12}) is None
    t, bound = workcount.least_seconds(
        work, {"hbm_bytes_per_s": 1e12, "vpu_int32_compare_per_s": 1e12})
    assert (t, bound) == (1e-3, "compute")
    t, bound = workcount.least_seconds(
        work, {"hbm_bytes_per_s": 1e8, "vpu_int32_compare_per_s": 1e12})
    assert (t, bound) == (1e-2, "memory")
