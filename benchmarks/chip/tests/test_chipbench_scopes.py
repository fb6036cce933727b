"""The readers of the program's named layer scopes (``scopes.py`` and the
six metrics that use it), on small traces recorded on a TPU v5 lite with
``record_fixture.py``: two steps each of ``md216k_1chip`` and
``vic256_1chip`` and one of ``md857k_slab4`` (four devices). On the two
older fixtures, recorded before the program named its layers, every one of
them reads nothing."""
import pytest

import chipbench_tiny as T
import scopes as S
from test_chipbench_trace import Ctx, read

DT = T.run.DT
FIX = T.HERE / "fixtures"

MD_READERS = ("cell_list_ms.md", "candidate_gather_ms.md",
              "slot_scatter_ms.md", "exchange_ms.md")
VIC_READERS = ("bucketing_ms.vic", "m4_unbucket_ms.vic")
SPLIT_READERS = MD_READERS[:3]
CELLS = {"md216k_scoped": ("md_lj_216k", 1),
         "vic256_scoped": ("vic_ring_256", 1),
         "md857k_slab4_scoped": ("md_lj_857k_slab4", 4),
         "md216k_fixture": ("md_lj_216k", 1),
         "vic256_fixture": ("vic_ring_256", 1)}


def _ctx(name):
    config, chips = CELLS[name]
    tr = DT.load_fixture(str(FIX / f"{name}.json.gz"))
    steps = sum(1 for s in tr.host_spans if s[0] == "step")
    cfg = T.run.MF._read(T.BENCH / "configs" / f"{config}.json")
    return Ctx(tr, steps, cfg, chips=chips)


def _value(v):
    return v["value"] if isinstance(v, dict) else v


def test_segments():
    seg = S.segments("jit(step)/shard_map/pair_boundary/vmap(cell_list)/"
                     "jit(argsort)/sort;jit(step)/vmap(vmap(map))/add")
    assert {"pair_boundary", "cell_list", "map", "jit(argsort)",
            "shard_map"} <= seg
    assert "argsort" not in seg and "step" not in seg
    assert not S.segments("jit(build_cell_list)/gather") & S.LAYER_SCOPES


def _op(name, kind, op_name, start, dur):
    return DT.Op(name, kind, "", op_name, "jit_step", start, dur)


def test_shared_loop_body_goes_to_its_call_site():
    """Two binary searches of one length share their loop body in XLA,
    whose ops then carry one site's name stack for both: each loop's body
    counts where its ``while`` was called, as on the slab step's
    ``map()`` and interior cell list."""
    body = ("s/map/jit(searchsorted)/cell_list/jit(searchsorted)/while/"
            "body/gather")
    ops = [_op("while.1", "while", "s/map/jit(searchsorted)/while", 0, 10),
           _op("fusion.1", "fusion", body, 1, 8),
           _op("while.2", "while", "s/pair_interior/jit(build_cell_list)/"
               "cell_list/jit(searchsorted)/while", 20, 30),
           _op("fusion.1", "fusion", body, 21, 28)]
    ctx = Ctx(DT.DeviceTrace(devices=[ops], host_spans=[("step", 0, 60)]),
              1, {})
    assert read("exchange_ms.md", ctx) == pytest.approx(10 / 1e6)
    assert read("cell_list_ms.md", ctx) == pytest.approx(
        {"value": 30 / 1e6, "interior": 30 / 1e6})


def test_unscoped_conditional_keeps_its_ops_scopes():
    """A reuse step's ``lax.cond`` sits above every layer: the ops of its
    branches keep their own scopes, and a loop inside a branch still
    counts where it was called."""
    shared = "s/cond/map/jit(searchsorted)/while/body/gather"
    ops = [_op("conditional.1", "conditional", "s/cond", 0, 100),
           _op("fusion.1", "fusion", "s/cond/branch_1/cell_list/sort", 1, 9),
           _op("while.1", "while", "s/cond/branch_1/pair_boundary/"
               "cell_list/jit(searchsorted)/while", 10, 20),
           _op("fusion.2", "fusion", shared, 11, 18),
           _op("fusion.3", "fusion", "s/cond/branch_1/map/add", 40, 5),
           _op("fusion.4", "fusion", "s/cond/branch_1/candidate_gather/"
               "gather", 50, 30)]
    ctx = Ctx(DT.DeviceTrace(devices=[ops], host_spans=[("step", 0, 100)]),
              1, {})
    assert read("cell_list_ms.md", ctx) == pytest.approx(
        {"value": 29 / 1e6, "boundary": 20 / 1e6})
    assert read("exchange_ms.md", ctx) == pytest.approx(5 / 1e6)
    assert read("candidate_gather_ms.md", ctx) == pytest.approx(
        {"value": 30 / 1e6})


def test_exchange_needs_its_scopes_on_several_chips():
    """One chip runs no exchange and reads 0; a trace of several chips
    that names layers but not ``map`` / ``ghost_get`` reads nothing."""
    dev = [_op("fusion.1", "fusion", "s/cell_list/sort", 0, 10)]
    one = Ctx(DT.DeviceTrace(devices=[dev], host_spans=[("step", 0, 10)]),
              1, {})
    assert read("exchange_ms.md", one) == 0.0
    four = Ctx(DT.DeviceTrace(devices=[dev] * 4,
                              host_spans=[("step", 0, 10)]), 1, {}, chips=4)
    assert read("exchange_ms.md", four) is None


@pytest.mark.parametrize("name", ["md216k_fixture", "vic256_fixture"])
@pytest.mark.parametrize("metric", MD_READERS + VIC_READERS)
def test_old_traces_read_nothing(name, metric):
    ctx = _ctx(name)
    assert not S.named(ctx.trace)
    assert read(metric, ctx) is None


@pytest.mark.parametrize("name", ["md216k_scoped", "md857k_slab4_scoped"])
@pytest.mark.parametrize("metric", MD_READERS)
def test_md_scopes_read(name, metric):
    ctx = _ctx(name)
    assert ctx.steps >= 1 and len(ctx.trace.devices) == ctx.chips
    v = read(metric, ctx)
    assert v is not None and _value(v) >= 0
    if metric == "exchange_ms.md" and ctx.chips == 1:
        assert v == 0.0                   # one chip: no map() or ghost_get
    else:
        assert _value(v) > 0


@pytest.mark.parametrize("metric", SPLIT_READERS)
def test_slab_splits(metric):
    """The split-phase slab step's interior and boundary passes each hold
    a part of the layer; one chip has no such passes."""
    v = read(metric, _ctx("md857k_slab4_scoped"))
    assert v["interior"] > 0 and v["boundary"] > 0
    assert v["interior"] + v["boundary"] <= v["value"] * 1.0001
    one = read(metric, _ctx("md216k_scoped"))
    assert set(one) == {"value"}


@pytest.mark.parametrize("name,extra", [("md216k_scoped", ()),
                                        ("md857k_slab4_scoped",
                                         ("exchange_ms.md",))])
def test_engine_identity(name, extra):
    """The named layers cover the step engine's XLA work: cell list,
    candidate gather and slot scatter (with map() and ghost_get on four
    chips) make 85-100% of ``engine_xla_ms.md``."""
    ctx = _ctx(name)
    engine = read("engine_xla_ms.md", ctx)
    parts = sum(_value(read(m, ctx)) for m in SPLIT_READERS + extra)
    assert 0.85 * engine <= parts <= engine * 1.0001, (parts, engine)


@pytest.mark.parametrize("metric", VIC_READERS)
def test_vic_scopes_read(metric):
    ctx = _ctx("vic256_scoped")
    v = read(metric, ctx)
    assert v is not None and v > 0
    assert v < DT.union_ns(ctx.trace.devices[0]) / 1e6 / ctx.steps


@pytest.mark.parametrize("name,kernels", [
    ("md216k_scoped", ("cell_pair",)),
    ("md857k_slab4_scoped", ("cell_pair",)),
    ("vic256_scoped", ("m4_p2m", "m4_m2p"))])
def test_kernels_are_named(name, kernels):
    """Every Pallas kernel carries its name in its name stack, and the
    accepted readers still find theirs."""
    ctx = _ctx(name)
    for k in kernels:
        ops = [o for ops in ctx.trace.devices for o in ops
               if DT.is_pallas(o) and k in S.segments(o.op_name)]
        assert len(ops) >= ctx.steps * ctx.chips, k
    if name.startswith("md"):
        assert read("pair_kernel_ms.md", ctx) > 0
    else:
        assert read("m4_kernel_ms.vic", ctx) > 0
        assert read("fft_ms.vic", ctx) > 0
