"""Device time of M2P's un-bucketing per step, ms: the ops under the
``m4_unbucket`` scope, the scatter-add of the interpolated slot values
back to the particles."""
import scopes as S


def read(ctx):
    return S.scope_ms(ctx, ("m4_unbucket",))
