"""Device time of the M'4 Pallas kernels (P2M and M2P) per step, ms."""
import devtrace as DT


def is_m4(op):
    return DT.is_pallas(op) and any(
        k in op.name or k in op.op_name for k in ("p2m_cells", "m2p_cells"))


def read(ctx):
    return DT.per_step_ms(ctx, is_m4)
