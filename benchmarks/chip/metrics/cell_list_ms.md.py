"""Device time of cell-list builds per step, ms (mean over the chips
used): the ops under the ``cell_list`` scope of ``build_cell_list``. On
the split-phase slab step, ``interior`` is the locals-only list of the
interior pass and ``boundary`` the combo list of the boundary pass."""
import scopes as S


def read(ctx):
    return S.with_splits(ctx, "cell_list")
