"""Work the M'4 interpolation of one vortex-in-cell step needs, from the
configuration alone (never from the kernel's tiles).

A remeshed step (every node re-seeded) makes 2 mesh-to-particle
interpolations of 6 channels (u and the RHS at the start and at the
predicted positions) and 2 particle-to-mesh interpolations of 3 channels
(the predicted and the final vorticity). Each reaches the 4³ nodes of
every particle.

Operations per particle and interpolation: base and fraction 12 (3 axes ×
subtract, divide, floor, subtract); the 4 M'4 weights of each axis 96
(3 × 4 × 8: |s| 2, s² 1, s³ 1, two products 2, two sums 2); the 4³
weight products 128 (2 each); per channel a multiply-add per node,
128 × channels.
Bytes per interpolation: positions (3 float32) and the particles' values
read or written once, and the mesh field written or read once.
"""
import math

CALLS = (("m2p", 6), ("m2p", 6), ("p2m", 3), ("p2m", 3))
FLOPS_BASE = 12 + 96 + 128
FLOPS_PER_CHANNEL = 128


def count(config: dict) -> dict:
    nodes = math.prod(config["shape"])
    particles = nodes
    flops = sum(particles * (FLOPS_BASE + FLOPS_PER_CHANNEL * ch)
                for _, ch in CALLS)
    nbytes = sum(4 * (particles * (3 + ch) + nodes * ch) for _, ch in CALLS)
    return {"flops": flops, "bytes": nbytes}
