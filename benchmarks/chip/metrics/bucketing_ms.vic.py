"""Device time of the M'4 interpolation bucketing per step, ms: the ops
under the ``m4_bucketing`` scope of ``bucket_particles`` (the cell list
over the particles and the slot gathers)."""
import scopes as S


def read(ctx):
    return S.scope_ms(ctx, ("m4_bucketing",))
