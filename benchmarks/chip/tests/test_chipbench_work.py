"""The roofline's work counts against hand counts, and the interval and
matching arithmetic of the trace reduction on a small hand-made trace."""
import pytest

import chipbench_tiny as T

DT = T.run.DT


def test_lattice_neighbours_by_hand():
    pk = DT.load_module("work", "pair_kernel")
    # |v|^2 <= 6 < 2.55^2: 6 + 12 + 8 + 6 + 24 + 24 lattice vectors
    assert pk.lattice_neighbours(0.1, 0.255) == 80
    assert pk.lattice_neighbours(1.0, 1.01) == 6
    w = pk.count({"n_per_side": 60, "box": 6.0, "sigma": 0.085})
    assert w["pairs"] == 8_640_000
    assert w["flops"] == 25 * 8_640_000
    assert w["bytes"] == 216_000 * 24
    w4 = pk.count({"n_per_side": 95, "box": 9.5, "sigma": 0.085})
    assert w4["pairs"] == 95 ** 3 * 40


def test_m4_work_by_hand():
    mk = DT.load_module("work", "m4_kernel")
    n = 256 * 64 * 64
    w = mk.count({"shape": [256, 64, 64]})
    per_particle = 2 * (236 + 128 * 6) + 2 * (236 + 128 * 3)
    assert w["flops"] == n * per_particle
    assert w["bytes"] == 4 * n * (2 * (3 + 6 + 6) + 2 * (3 + 3 + 3))


def _op(name, kind, start, dur, target="", op_name=""):
    return DT.Op(name, kind, target, op_name, "jit_step", start, dur)


def test_union_counts_nested_ops_once():
    ops = [_op("while.1", "while", 0, 100),
           _op("fusion.1", "fusion", 10, 20),      # inside the while
           _op("fusion.2", "fusion", 150, 50),
           _op("fusion.3", "fusion", 180, 40)]     # overlaps fusion.2
    assert DT.union_ns(ops) == 100 + 70
    assert DT.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_matchers():
    pallas = _op("step.1", "custom-call", 0, 1, target="tpu_custom_call")
    other = _op("custom-call.3", "custom-call", 0, 1,
                target="ConcatBitcast")
    cp = _op("collective-permute-done.2", "collective-permute-done", 0, 1)
    ar = _op("all-reduce.1", "all-reduce", 0, 1)
    assert DT.is_pallas(pallas) and not DT.is_pallas(other)
    assert DT.is_collective(cp) and DT.is_collective(ar)
    assert not DT.is_collective(pallas)
    assert DT.parse_instruction(
        '%step.1 = f32[3,8]{1,0} custom-call(f32[3,8]{1,0} %p), '
        'custom_call_target="tpu_custom_call"') == (
            "step.1", "custom-call", "tpu_custom_call")
    assert DT.parse_instruction(
        "%collective-permute-done.2 = f32[4]{0} collective-permute-done("
        "(f32[4]{0}, f32[4]{0}) %collective-permute-start.2)")[1] == \
        "collective-permute-done"


def test_op_names_from_compiled_hlo():
    text = ("HloModule jit_vic_step, entry_computation_layout={}\n"
            "  %convolution_add_fusion.7 = f32[3]{0} fusion(f32[3]{0} %a), "
            'kind=kOutput, metadata={op_name="jit(vic_step)/'
            'jit(fft_poisson)/jit(fft)" source_file="x.py"}\n'
            "  ROOT %tuple = (f32[3]{0}) tuple(%b)\n")
    names = DT.op_names_from_hlo([text])
    assert names == {("jit_vic_step", "convolution_add_fusion.7"):
                     "jit(vic_step)/jit(fft_poisson)/jit(fft)"}


class _Ctx:
    def __init__(self, devices, steps, window_s, chips=1, peaks=None):
        self.trace = DT.DeviceTrace(devices=devices, host_spans=[])
        self.steps, self.window_s, self.chips = steps, window_s, chips
        self.peaks = peaks


def test_idle_share_and_per_step():
    d0 = [_op("a", "fusion", 0, 4e8), _op("k", "custom-call", 5e8, 2e8,
                                          target="tpu_custom_call")]
    d1 = [_op("a", "fusion", 0, 8e8)]
    ctx = _Ctx([d0, d1], steps=2, window_s=1.0)
    # busy 0.6 s and 0.8 s of a 1 s window: idle 30% on average
    assert DT.idle_share_pct(ctx) == pytest.approx(30.0)
    assert DT.per_step_ms(ctx, DT.is_pallas) == pytest.approx(50.0)
    assert DT.per_step_ms(ctx, DT.is_pallas, over="max") == \
        pytest.approx(100.0)
    assert DT.per_step_ms(ctx, DT.is_collective) is None
    empty = _Ctx([[]], steps=1, window_s=1.0)
    assert DT.idle_share_pct(empty) is None


def test_roofline_names_its_bound_and_never_reads_zero():
    ctx = _Ctx([[]], steps=1, window_s=1.0, chips=2,
               peaks={"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0})
    r = DT.roofline_pct(ctx, flops=200.0, nbytes=100.0, kernel_ms=10_000.0)
    assert r["bound"] == "memory"               # 5 s against 1 s
    assert r["value"] == pytest.approx(50.0)
    assert DT.roofline_pct(ctx, 1.0, 1.0, None) is None
