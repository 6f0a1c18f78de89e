"""The roofline's byte count against a count by hand, and the peaks."""
import numpy as np
import pytest

import reference
import roofline


def test_expansion_bytes_by_hand():
    # triangle 0-1-2 plus the tail 2-3: degrees 2, 2, 3, 1
    g = reference.HostGraph(4, [0, 0, 0, 0], [[0, 1], [0, 2], [1, 2], [2, 3]])
    np.testing.assert_array_equal(g.deg, [2, 2, 3, 1])
    verts = np.arange(4)[:, None]
    # motifs to 3: level 1 reads every list once (2m = 8 ids); level 2
    # reads both lists of each edge: (2+2) + (2+3) + (2+3) + (3+1) = 18
    # ids, and reads the 4 edges back, 8 ids: 34 ids, 136 bytes
    assert roofline.expansion_bytes(g.deg, [verts, g.edges]) == 4 * 34
    # cliques to 4 adds the triangle (0, 1, 2): 2 + 2 + 3 = 7 ids read
    # and 3 ids written and read: 44 ids
    tri = reference.triangles(g)
    np.testing.assert_array_equal(tri, [[0, 1, 2]])
    assert roofline.expansion_bytes(g.deg, [verts, g.edges, tri]) == 4 * 44


def test_peaks_keyed_by_kind():
    p = roofline.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v4")
