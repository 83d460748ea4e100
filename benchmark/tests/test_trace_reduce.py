import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000


def _plane():
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", 10 * MS, 20 * MS),
           # overlaps the first
           ("%fusion.2 = f32[8]{0} fusion(...)", 25 * MS, 15 * MS),
           # nested in the union so far
           ("%copy.7 = f32[8]{0} copy(...)", 30 * MS, 5 * MS),
           # after a gap of 20 ms
           ("%ragged_paged_attention.24 = bf16[1] custom-call(...)",
            60 * MS, 30 * MS),
           ("%ragged_paged_attention.28 = bf16[1] custom-call(...)",
            90 * MS, 5 * MS)]
    host = [("bench.step", 0, 45 * MS), ("bench.look", 45 * MS, 13 * MS),
            ("bench.step", 58 * MS, 42 * MS), ("other", 0, 500 * MS)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_run(1)", 0, 99 * MS)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]


def test_stem():
    assert tr.stem("%fusion.123 = f32[2]{0} fusion(%a)") == "fusion"
    assert tr.stem("%ragged_paged_attention.24 = bf16[8] custom-call()") \
        == "ragged_paged_attention"
    assert tr.stem("copy-start.5.1") == "copy-start"
    assert tr.stem("jit_run(123)") == "jit_run(123)"


def test_union_merges_overlapping_and_nested():
    assert tr.union([(25, 40), (10, 30), (30, 35), (60, 90)]) \
        == [[10, 40], [60, 90]]


def test_reduce_by_hand():
    got = tr.reduce(_plane())
    # window: the harness's marks, 0 .. 100 ms; busy: 10-40 and 60-95
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.065)
    assert got["chips"] == 1
    assert got["ops_s"]["ragged_paged_attention"] == pytest.approx(0.035)
    assert got["ops_s"]["fusion"] == pytest.approx(0.035)
    assert got["device_ops"][0][1] == pytest.approx(0.035)
    # gaps: 40-60 (20 ms: step holds 5, look 13 -> look), 0-10 (step),
    # 95-100 (step)
    assert [round(g[1], 6) for g in got["idle_gaps"]] \
        == [0.020, 0.010, 0.005]
    assert got["idle_gaps"][0][0] == "bench.look"
    assert got["idle_gaps"][1][0] == "bench.step"


def test_no_device_operation_reads_as_nothing():
    planes = [p for p in _plane() if p["name"] != "/device:TPU:0"]
    assert tr.reduce(planes) is None
    from benchmark.readers import device_idle_share, op_time_share
    assert device_idle_share.read({"trace": None}) is None
    assert op_time_share.read({"trace": None}, pattern="x") is None


def test_readers_on_the_hand_made_trace():
    from benchmark.readers import device_idle_share, op_time_share
    obs = {"trace": tr.reduce(_plane())}
    assert device_idle_share.read(obs) == pytest.approx(35.0)
    assert op_time_share.read(obs, pattern="^ragged_paged_attention") \
        == pytest.approx(35.0)
    assert op_time_share.read(obs, pattern="^no_such_kernel") is None
