"""The benchmark's operation and byte counts against the program's own
D-slash model and against the bytes of the arrays a hop touches."""
import numpy as np
import pytest

import chipbench_helpers  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import work
from repro.lqcd.dirac import dslash_bytes_per_site, dslash_flops_per_site


def test_hop_flops_are_the_programs_count():
    assert work.HOP_FLOPS == dslash_flops_per_site() == 1320
    lat = (8, 8, 8, 8)
    assert work.hop(lat, "float32").flops == 1320 * 8 ** 4 / 2


@pytest.mark.parametrize("dtype,real_bytes", [("float32", 4),
                                              ("bfloat16", 2)])
def test_hop_bytes_are_one_pass_over_its_arrays(dtype, real_bytes):
    """A half-lattice hop reads its source half-spinor and the links of
    both parities once and writes its output once: the bytes of those
    arrays at the declared precision, from their shapes."""
    lat = (4, 6, 4, 8)
    vh = int(np.prod(lat)) // 2
    spinor = vh * 4 * 3 * 2 * real_bytes
    links = 2 * (4 * vh * 3 * 3 * 2 * real_bytes)      # both parities
    assert work.hop(lat, dtype).bytes == 2 * spinor + links
    # never more than the program's model, which streams 8 neighbour
    # spinors and the output twice per site
    assert work.HOP_REALS * real_bytes <= dslash_bytes_per_site(
        real_bytes, compressed_links=False)


def test_solve_is_built_from_its_parts():
    lat = (4, 4, 4, 4)
    one = work.solve(lat, 10, 2, "bfloat16", "float32")
    two = work.solve(lat, 20, 4, "bfloat16", "float32")
    fixed = work.solve(lat, 0, 0, "bfloat16", "float32")
    inner = work.inner_cg(lat, 10, "bfloat16")
    assert two.bytes - one.bytes == one.bytes - fixed.bytes
    assert fixed.bytes > 0 and inner.bytes < one.bytes - fixed.bytes
    # the inner iteration: 4 hops and the vector updates at 2 bytes a real
    per_iter = inner.bytes / 10
    assert per_iter == (4 * work.HOP_REALS + 2 * 24 + 7 * 24) * 128 * 2
    w = work.Work(2e12, 8.19e11)
    assert w.seconds_at(1.97e14, 8.19e11) == 1.0
