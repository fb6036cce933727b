"""The per-layer readers on small traces recorded on a TPU v5 lite (two
steps of each cell, reduced by ``devtrace.from_xplane`` and saved with
``DeviceTrace.to_json``)."""
import pytest

import chipbench_tiny as T

DT = T.run.DT
FIX = T.HERE / "fixtures"


class Ctx:
    def __init__(self, trace, steps, config, chips=1):
        self.trace, self.steps, self.config, self.chips = (
            trace, steps, config, chips)
        spans = trace.host_spans
        self.window_s = (max(s[2] for s in spans) - spans[0][1]) / 1e9
        self.peaks = T.run.peaks_for("TPU v5 lite")


def _ctx(name, config):
    tr = DT.load_fixture(str(FIX / f"{name}.json.gz"))
    steps = sum(1 for s in tr.host_spans if s[0] == "step")
    cfg = T.run.MF._read(T.BENCH / "configs" / f"{config}.json")
    return Ctx(tr, steps, cfg)


def read(name, ctx):
    return DT.load_module("metrics", name).read(ctx)


def test_md_trace():
    ctx = _ctx("md216k_fixture", "md_lj_216k")
    assert ctx.steps == 2
    ops = ctx.trace.devices[0]
    assert sum(DT.is_pallas(o) for o in ops) == ctx.steps
    busy = DT.union_ns(ops) / 1e9
    assert 0 < busy <= ctx.window_s
    idle = read("device_idle_share.md", ctx)
    assert 0 < idle < 100
    kernel = read("pair_kernel_ms.md", ctx)
    engine = read("engine_xla_ms.md", ctx)
    assert kernel > 0 and engine > kernel
    assert kernel + engine == pytest.approx(busy * 1e3 / ctx.steps)
    roof = read("pair_kernel_roofline.md", ctx)
    assert roof["bound"] == "memory" and 0 < roof["value"] < 100
    assert read("collective_ms.md", ctx) is None      # one chip


def test_vic_trace():
    ctx = _ctx("vic256_fixture", "vic_ring_256")
    m4 = read("m4_kernel_ms.vic", ctx)
    fft = read("fft_ms.vic", ctx)
    assert m4 > 0 and fft > 0
    assert m4 + fft < DT.union_ns(ctx.trace.devices[0]) / 1e6 / ctx.steps
    assert all("fft" in o.op_name for o in ctx.trace.devices[0]
               if "jit(fft_poisson)" in o.op_name)
    roof = read("m4_kernel_roofline.vic", ctx)
    assert 0 < roof["value"] < 100
    assert 0 < read("device_idle_share.vic", ctx) < 100


def test_breakdown_lists():
    ctx = _ctx("vic256_fixture", "vic_ring_256")
    top = DT.top_ops(ctx.trace)
    assert 0 < len(top) <= 10 and all(s > 0 for _, s in top)
    assert top == sorted(top, key=lambda r: -r[1])
    gaps = DT.idle_gaps(ctx.trace)
    assert len(gaps) <= 10 and all(name in ("step", "sample", "none")
                                   for name, _ in gaps)


def _op(text, start, dur):
    name, kind, target = DT.parse_instruction(text)
    return DT.Op(name, kind, target, "", "jit_step", float(start), float(dur))


def test_collectives_across_devices():
    """Four devices, one step each: a pair kernel, XLA work, and an async
    collective-permute whose -start and -done sit on the device's op line
    (the -done holding the wait), as a TPU trace records them."""
    devices = []
    for d in range(4):
        wait = 1e6 * (d + 1)
        devices.append([
            _op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                0, 4e6),
            _op("%collective-permute-start.3 = (f32[8]{0}, f32[8]{0}) "
                "collective-permute-start(f32[8]{0} %a), channel_id=1",
                4e6, 1e5),
            _op('%custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %b), '
                'custom_call_target="tpu_custom_call"', 4.1e6, 2e6),
            _op("%collective-permute-done.3 = f32[8]{0} "
                "collective-permute-done((f32[8]{0}, f32[8]{0}) %c)",
                6.1e6, wait),
        ])
    tr = DT.DeviceTrace(devices=devices,
                        host_spans=[("step", 0.0, 12e6)])
    ctx = Ctx(tr, 1, {}, chips=4)
    assert read("collective_ms.md", ctx) == pytest.approx(0.1 + 4.0)
    assert read("pair_kernel_ms.md", ctx) == pytest.approx(2.0)
    assert read("engine_xla_ms.md", ctx) == pytest.approx(4.0)
