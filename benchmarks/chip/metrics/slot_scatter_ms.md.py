"""Device time of the pair engine's slot-to-particle scatter per step, ms
(mean over the chips used): the ops under the ``slot_scatter`` scope, with
the ``interior`` / ``boundary`` passes of the split-phase slab step
apart."""
import scopes as S


def read(ctx):
    return S.with_splits(ctx, "slot_scatter")
