"""Device time under the program's named layer scopes, for the per-layer
metric readers under ``metrics/``.

The program wraps each layer of its hot path in a ``jax.named_scope``
(PERF.md §3), so the ``op_name`` of every HLO instruction a layer emits
holds the scope's name as one segment of its name stack, such as
``jit(local_step)/shard_map/pair_boundary/cell_list/jit(argsort)/sort``, or
``vmap(candidate_gather)`` under a transform. Fused instructions join
several stacks with ``;``. A trace of a program that names no layer (an
older commit) holds none of the scopes, and the readers return None.

XLA may share one loop body between call sites (the binary searches of
``map()``'s bucket packing and of a cell list of the same length do), and
then the body's instructions carry the name stack of one site only. So an
op that runs inside a ``while``, ``conditional`` or ``call`` is attributed
by the name stack of the innermost such op around it whose stack names a
layer, which is its call site. A container that names none (the
``lax.cond`` of a reuse step, above every layer) leaves its ops their own
stacks.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Iterable, List, Optional, Tuple

import devtrace as DT

LAYER_SCOPES = frozenset((
    "cell_list", "candidate_gather", "pair_kernel", "slot_scatter",
    "pair_interior", "pair_boundary", "map", "ghost_get", "advance",
    "finish", "counters", "m4_bucketing", "m4_p2m", "m4_m2p", "m4_unbucket",
    "poisson", "stencil", "remesh"))
SPLITS = (("interior", "pair_interior"), ("boundary", "pair_boundary"))

_TRANSFORM = re.compile(r"(?!p?jit\()\w+\((.+)\)")
_CONTAINERS = ("while", "conditional", "call")


@functools.lru_cache(maxsize=None)
def segments(op_name: str) -> frozenset:
    """The name-stack segments of an ``op_name``; a segment under a
    transform (``vmap(x)``) counts as ``x``, a jitted function's
    ``jit(f)`` stays as it is."""
    out = set()
    for path in op_name.split(";"):
        for seg in path.split("/"):
            m = _TRANSFORM.fullmatch(seg)
            while m:
                seg = m.group(1)
                m = _TRANSFORM.fullmatch(seg)
            out.add(seg)
    return frozenset(out)


def hlo_segments(hlo_text: str) -> frozenset:
    """Every name-stack segment of a compiled HLO text's ``op_name``
    metadata (the tests check the program's scopes with it)."""
    return frozenset().union(
        *map(segments, re.findall(r'op_name="([^"]*)"', hlo_text)))


def attributed(ops: Iterable) -> List[Tuple[object, str]]:
    """(op, the name stack it is attributed to) for one device's ops."""
    out, open_ = [], []          # open containers: (end_ns, op_name)
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        while open_ and o.end_ns > open_[-1][0]:
            open_.pop()
        out.append((o, open_[-1][1] if open_ else o.op_name))
        if o.kind in _CONTAINERS and segments(o.op_name) & LAYER_SCOPES:
            open_.append((o.end_ns, o.op_name))
    return out


def named(trace) -> bool:
    """Whether the traced program names its layers at all."""
    return any(segments(o.op_name) & LAYER_SCOPES
               for ops in trace.devices for o in ops)


def scope_ms(ctx, scopes: Iterable[str], within: Optional[str] = None,
             exclude: Callable = lambda op: False) -> Optional[float]:
    """Device ms per window step (mean over the chips used) of the union
    of the ops under any of ``scopes`` (and under ``within``, if given),
    less those ``exclude`` picks; None where no op matches."""
    scopes = frozenset(scopes)

    def hit(op, name):
        seg = segments(name)
        return (bool(seg & scopes) and (within is None or within in seg)
                and not exclude(op))

    per_dev = [DT.union_ns(o for o, name in attributed(ops) if hit(o, name))
               for ops in ctx.trace.devices]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / 1e6 / ctx.steps


def with_splits(ctx, scope: str) -> Optional[dict]:
    """``{"value": ms}`` of ``scope``, plus its ``interior`` and
    ``boundary`` shares where the step runs the split-phase slab
    schedule's ``pair_interior`` / ``pair_boundary`` passes."""
    total = scope_ms(ctx, (scope,))
    if total is None:
        return None
    out = {"value": total}
    for key, part in SPLITS:
        ms = scope_ms(ctx, (scope,), within=part)
        if ms is not None:
            out[key] = ms
    return out
