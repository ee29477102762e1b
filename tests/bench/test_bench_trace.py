"""The reduction from a profiler trace to the per-layer numbers: busy
union, exposed collective time, codec kernel matching and idle-gap
attribution, on hand-made traces and on one recorded on the chip."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.lib import trace  # noqa: E402

MS = 1_000_000       # ns


def _compact(devices, host):
    return {"devices": devices, "host": host, "lines": {}}


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert trace.length([(0, 4), (5, 7)]) == 6
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]


def _op(name, opcode, operands=""):
    return f"%{name} = f32[8]{{0:T(128)}} {opcode}({operands})"


def test_busy_exposed_codec_and_gaps():
    host = [("bench.input", 0, 1 * MS), ("bench.dispatch", 1 * MS, 2 * MS),
            ("bench.wait", 2 * MS, 10 * MS)]
    dev0 = [(_op("fusion.1", "fusion", "f32[8] %all-reduce.9"), 1 * MS,
             3 * MS),
            (_op("all-reduce-start.2", "all-reduce-start"), 2 * MS,
             5 * MS),                                      # 3..5 exposed
            (_op("bq_encode_pallas.7", "custom-call"), 6 * MS, 7 * MS),
            (_op("collective-permute-done.1", "collective-permute-done"),
             6 * MS, 8 * MS)]                              # 7..8 exposed
    dev1 = [(_op("fusion.1", "fusion"), 0, 10 * MS)]
    r = trace.reduce(_compact([dev0, dev1], host))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_per_device"] == pytest.approx([0.006, 0.010])
    assert r["busy_s"] == pytest.approx(0.008)
    assert r["collective_exposed_s"] == pytest.approx((0.003 + 0) / 2)
    assert r["codec_s"] == pytest.approx(0.0005)
    assert r["input_s"] == pytest.approx(0.001)
    gaps = dict((round(s, 6), n) for n, s in r["breakdown"]["idle_gaps"])
    # device 0 idles 0..1 (host making the batch), 5..6 and 8..10 (wait)
    assert gaps == {0.001: "bench.input", 0.002: "bench.wait"} or \
        sorted(gaps) == [0.001, 0.002]
    names = [trace.op_name(n) for n, _ in r["breakdown"]["device_ops"]]
    assert names[0] == "fusion.1"


def test_operation_names_and_opcodes():
    text = ("%fusion.256 = (bf16[8]{0:T(1024)(128)(2,1)}, f32[8]{0}) "
            "fusion(f32[8]{0} %all-reduce.3), kind=kLoop")
    assert trace.op_name(text) == "fusion.256"
    assert trace.opcode(text) == "fusion"
    assert trace.opcode("%a = f32[64]{0:T(128)S(1)} all-gather-start(x)") \
        == "all-gather-start"
    for name in ("bq_encode_pallas.3", "bq_decode_pallas",
                 "bq_decode_add_encode_pallas.12", "bq_decode_add_pallas.1"):
        assert trace.CODEC.match(name), name
    for name in ("fusion.12", "all-gather.1", "bq_encode.3"):
        assert not trace.CODEC.match(name), name


RECORDED = (pathlib.Path(__file__).resolve().parents[2] / "bench" /
            "testdata" / "minitron4b.1chip.opt8.trace.json.gz")


def test_reduction_of_a_trace_recorded_on_the_chip():
    """Four steps of minitron4b.1chip.opt8 on a TPU v5e: the optimizer's
    8-bit state runs four bq Pallas kernels a step, nothing collective."""
    t = trace.read_saved(str(RECORDED))
    r = trace.reduce(t)
    assert len(t["devices"]) == 1
    assert r["window_s"] == pytest.approx(3.370218812)
    assert r["busy_s"] == pytest.approx(3.353965035)
    assert r["collective_exposed_s"] == 0.0
    kernels = [(trace.op_name(n), e - s) for n, s, e in t["devices"][0]
               if trace.CODEC.match(trace.op_name(n))]
    assert len(kernels) == 16                      # 4 kernels x 4 steps
    assert r["codec_s"] == pytest.approx(sum(d for _, d in kernels) * 1e-9)
    assert r["codec_s"] == pytest.approx(1.873386214)
    # the device waits for the first batch the host makes
    name, secs = r["breakdown"]["idle_gaps"][0]
    assert name == "bench.input" and secs == pytest.approx(0.0137528, rel=1e-4)
    assert len(r["breakdown"]["device_ops"]) == 10


def test_exposed_collectives_of_a_four_chip_trace():
    """Device 0 of four steps of minitron-4b on dp=2 x tp=2 under
    `zhybrid_16_8`, recorded on a TPU v5e host: collective-permute ring
    hops, all-gathers and all-reduces beside the bq kernels."""
    t = trace.read_saved(str(RECORDED.parent /
                             "minitron4b.dp2tp2.zhybrid_16_8.device0"
                             ".trace.json.gz"))
    r = trace.reduce(t)
    ops = t["devices"][0]
    coll = [n for n, _, _ in ops if trace.COLLECTIVE.match(trace.opcode(n))]
    assert {trace.opcode(n) for n in coll} == {
        "collective-permute-start", "collective-permute-done",
        "all-gather", "all-reduce"}
    assert r["window_s"] == pytest.approx(2.988310269)
    assert r["busy_s"] == pytest.approx(2.970255454)
    # most collective time hides under other operations
    assert r["collective_exposed_s"] == pytest.approx(0.044941637)
    assert r["codec_s"] == pytest.approx(1.811399339)
