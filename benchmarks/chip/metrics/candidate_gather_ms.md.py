"""Device time of the pair engine's candidate gather per step, ms (mean
over the chips used): the ops under the ``candidate_gather`` scope of
``gather_cell_tiles``, with the ``interior`` / ``boundary`` passes of the
split-phase slab step apart."""
import scopes as S


def read(ctx):
    return S.with_splits(ctx, "candidate_gather")
