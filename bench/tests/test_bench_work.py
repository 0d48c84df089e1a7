"""The work count behind ``engine_roofline``, worked by hand."""
import numpy as np

from bench import reference, work


def test_greedy_bytes_by_hand():
    # G = 10 granules, A = 5 attributes, 1 core attribute, 2 iterations:
    # iteration 0 reads 4 candidates, iteration 1 reads 3, each over the
    # 10 granules at 1 byte, plus 10 granules x (4 + 4 + 1) bytes each time.
    assert work.greedy_bytes(10, 5, 1, 2) == (4 * 10 + 90) + (3 * 10 + 90)
    assert work.greedy_bytes(10, 5, 1, 0) == 0


def test_greedy_bytes_of_a_tiny_granule_table():
    # 8 rows of (x0, x1, x2) with d = x0 xor x1 and x2 a copy of x0:
    # 4 granules, weights 4, 2, 1, 1.  Without x1, (x0, x2) cannot tell
    # d, so x1 is the core; after it, x0 and x2 both give Θ(D|C) = 0 and
    # the lower index, x0, is picked: one greedy iteration, 2 candidates.
    x = np.array([[0, 0, 0]] * 4 + [[0, 1, 0]] * 2 + [[1, 0, 1], [1, 1, 1]])
    d = np.array([0] * 4 + [1] * 2 + [1, 0])
    gx, gd, gw = reference.granules(x, d)
    assert list(gw) == [4, 2, 1, 1]
    r = reference.reduce(gx, gd, gw, delta="SCE", v_max=2)
    assert r["core"] == [1] and r["reduct"] == [1, 0]
    iterations = len(r["reduct"]) - len(r["core"])
    # 2 candidates x 4 granules x 1 byte, and 4 granules x (4 + 4 + 1)
    assert work.greedy_bytes(len(gw), 3, len(r["core"]), iterations) \
        == 2 * 4 + 4 * 9
