"""Work the Lennard-Jones pair evaluation of one MD step needs, from the
configuration alone (never from the kernel's tiles).

Operations: every unordered pair within r_cut once (Newton's third law),
25 floating-point operations each: displacement 3, r² 5, σ²/r² 1,
(σ/r)⁶ 2, 2(σ/r)¹² − (σ/r)⁶ 3, ×24ε 1, ÷r² 1, force vector 3, added to
one particle and subtracted from the other 6.
Bytes: positions read once and forces written once, float32.

The pair count is that of the simple-cubic start lattice: the number of
lattice vectors shorter than r_cut, times the particles, halved. A
liquid's count differs by its density fluctuations only.
"""
import itertools
import math

FLOPS_PER_PAIR = 25
BYTES_PER_PARTICLE = 2 * 3 * 4


def lattice_neighbours(spacing: float, r_cut: float) -> int:
    """Sites of a simple-cubic lattice within ``r_cut`` of a site."""
    k = int(math.ceil(r_cut / spacing))
    rc2 = (r_cut / spacing) ** 2
    return sum(1 for v in itertools.product(range(-k, k + 1), repeat=3)
               if 0 < v[0] ** 2 + v[1] ** 2 + v[2] ** 2 < rc2)


def count(config: dict) -> dict:
    n = config["n_per_side"] ** 3
    nb = lattice_neighbours(config["box"] / config["n_per_side"],
                            3.0 * config["sigma"])
    pairs = n * nb // 2
    return {"pairs": pairs, "flops": FLOPS_PER_PAIR * pairs,
            "bytes": BYTES_PER_PARTICLE * n}
